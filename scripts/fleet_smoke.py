#!/usr/bin/env python
"""Loopback smoke of the fleet observability plane (scripts/build/
ci.sh gate): ONE tenanted, observability-armed daemon on 127.0.0.1,
8 tenant driver processes (scripts/tenant_bench.py --driver) hammering
it with equal weights, and scripts/udafleet.py --once --json polled
against it — first mid-run (the live view must carry the CAP_OBS
sections while queues are formed), then post-run for the WDRR
fairness audit: every tenant's fleet share of scheduled bytes must
land within FAIR_TOL of its weight-proportional entitlement (equal
weights -> 1/8 each). Exit code != 0 on any gate failure.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tests.helpers import make_mof_tree  # noqa: E402
from uda_tpu.mofserver import DataEngine, DirIndexResolver  # noqa: E402
from uda_tpu.net import ShuffleServer  # noqa: E402
from uda_tpu.utils.config import Config  # noqa: E402

TENANTS = 8
FAIR_TOL = 0.02  # |share - entitlement|, absolute (the 2% acceptance)
NUM_MAPS = 1
RECORDS = 100
VAL_BYTES = 500
CHUNK = 4 << 20
DEPTH = 12
WARMUP_S = 0.5
WINDOW_S = 2.0


def tenant_name(i: int) -> str:
    return f"tenant{i:02d}"


def job_name(i: int) -> str:
    return f"jobFleet{i:02d}"


def udafleet_once(port: int) -> dict:
    """The literal ci gate: one scripts/udafleet.py --once --json run
    against the live daemon, parsed."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/udafleet.py"),
         f"127.0.0.1:{port}", "--once", "--json", "--window", "30",
         "--timeout", "10"],
        capture_output=True, text=True, timeout=60)
    if out.returncode != 0:
        print(f"FLEET SMOKE FAIL: udafleet exited {out.returncode}: "
              f"{out.stderr.strip()}")
        sys.exit(1)
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="uda_fleet_smoke_")
    for i in range(TENANTS):
        make_mof_tree(tmp, job_name(i), num_maps=NUM_MAPS,
                      num_reducers=1, records_per_map=RECORDS,
                      val_bytes=VAL_BYTES, seed=300 + i)
    engine = DataEngine(DirIndexResolver(tmp), Config())
    # the tenant_bench contention shape (small shared pool, byte-path
    # serves, small socket buffers) so WDRR queues actually form, PLUS
    # the observability plane armed: rollup ring on a fast interval so
    # the SLI book sees several intervals inside the driver window
    server = ShuffleServer(
        engine, Config({"uda.tpu.tenant.enable": True,
                        "uda.tpu.stats.enable": True,
                        "uda.tpu.ts.interval.s": 0.2,
                        "uda.tpu.net.zerocopy": False,
                        "uda.tpu.net.sockbuf.kb": 64,
                        "uda.tpu.tenant.wqe.total": TENANTS // 2}),
        host="127.0.0.1", port=0).start()
    rc = 0
    procs = []
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for i in range(TENANTS):
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(REPO, "scripts/tenant_bench.py"),
                 "--driver", "--port", str(server.port),
                 "--tenant", tenant_name(i), "--job", job_name(i),
                 "--maps", str(NUM_MAPS), "--chunk", str(CHUNK),
                 "--depth", str(DEPTH), "--weight", "1",
                 "--warmup", str(WARMUP_S), "--window", str(WINDOW_S)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env))
        # mid-run poll: the live fleet view, queues formed
        time.sleep(WARMUP_S + WINDOW_S * 0.5)
        live = udafleet_once(server.port)
        spec = f"127.0.0.1:{server.port}"
        if live["daemons"].get(spec) != "ok":
            print(f"FLEET SMOKE FAIL: daemon status "
                  f"{live['daemons'].get(spec)!r}, want 'ok'")
            return 1
        if not isinstance(live.get("anomalies"), list):
            print("FLEET SMOKE FAIL: no anomalies section")
            return 1
        for p in procs:
            p.wait(timeout=WARMUP_S + WINDOW_S + 60)
        # post-run poll: lifetime scheduled bytes are final — the
        # fairness audit the SLI book exists to answer
        final = udafleet_once(server.port)
        tenants = final.get("tenants", {})
        if len(tenants) < TENANTS:
            print(f"FLEET SMOKE FAIL: fleet view shows "
                  f"{len(tenants)}/{TENANTS} tenants: {sorted(tenants)}")
            return 1
        entitled = 1.0 / TENANTS
        worst = (None, 0.0)
        for t, agg in sorted(tenants.items()):
            share = agg.get("fleet_share")
            if share is None:
                print(f"FLEET SMOKE FAIL: tenant {t} has no fleet share")
                return 1
            dev = abs(share - entitled)
            if dev > worst[1]:
                worst = (t, dev)
            if dev > FAIR_TOL:
                print(f"FLEET SMOKE FAIL: tenant {t} share "
                      f"{share:.4f} deviates {dev:.4f} from the "
                      f"equal-weight entitlement {entitled:.4f} "
                      f"(tol {FAIR_TOL})")
                rc = 1
        if rc == 0:
            print(f"FLEET SMOKE OK: {TENANTS} tenants, worst share "
                  f"deviation {worst[1]:.4f} ({worst[0]}) within "
                  f"{FAIR_TOL} of entitlement; daemon ok, "
                  f"{len(final['anomalies'])} active anomalies")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
        engine.stop()
    return rc


if __name__ == "__main__":
    sys.exit(main())
