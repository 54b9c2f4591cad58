"""The MOFSupplier role, in a process of its own: one ``UdaBridge``
serving the map outputs under ``root`` over loopback TCP for the whole
run, as a NodeManager's supplier does. Never touches the accelerator
(the parent sets ``JAX_PLATFORMS=cpu``). Protocol with the parent:
prints ``{"port": n}``, serves until its stdin closes, sends EXIT,
prints ``{"failed": bool, "failure": str | null}``."""

from __future__ import annotations

import json
import os
import sys


class SupplierCallable:
    """The embedder's up-calls: net knobs through the conf pull channel
    (as a jobconf would carry them), index resolution by path."""

    def __init__(self, root: str):
        self.root = root
        self.failure = None

    def get_conf_data(self, name, default):
        return {"uda.tpu.net.listen": "true", "uda.tpu.net.port": "0",
                "uda.tpu.net.bind": "127.0.0.1"}.get(name, "")

    def get_path_uda(self, job_id, map_id, reduce_id):
        from uda_tpu.mofserver import read_index_file

        d = os.path.join(self.root, job_id, map_id)
        return read_index_file(os.path.join(d, "file.out.index"),
                               os.path.join(d, "file.out"))[reduce_id]

    def failure_in_uda(self, error):
        self.failure = error


def main(repo: str, root: str) -> int:
    sys.path.insert(0, repo)
    from uda_tpu.bridge import UdaBridge
    from uda_tpu.bridge.protocol import Cmd, form_cmd

    cb = SupplierCallable(root)
    supplier = UdaBridge()
    supplier.start(False, [], cb)
    supplier.do_command(form_cmd(Cmd.INIT, []))
    if supplier.failed or supplier.net_server() is None:
        print(f"supplier did not start: {cb.failure!r}", file=sys.stderr)
        return 1
    print(json.dumps({"port": supplier.net_server().port}), flush=True)
    sys.stdin.read()                      # until the parent closes it
    supplier.do_command(form_cmd(Cmd.EXIT, []))
    print(json.dumps({"failed": bool(supplier.failed),
                      "failure": None if cb.failure is None
                      else repr(cb.failure)[:500]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
