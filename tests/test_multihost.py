"""Cross-process exchange: 2 processes x 4 virtual CPU devices run the
distributed sort step over one global mesh (the reference's cross-node
RDMA data plane, SURVEY §2.3; jax.distributed replaces the rdma_cm
connect dance of reference src/DataNet/RDMAClient.cc:215-356)."""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # spawns real multi-process meshes


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("nprocs", [2, 4])
def test_multiprocess_cpu_exchange(nprocs):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(root, "tests", "multihost_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(nprocs), str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(nprocs)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"MULTIHOST-OK p{i}" in out, f"worker {i} output:\n{out}"
