"""chip_smoke.py's plumbing, on the CPU: the rehearsal runs end to end
at tiny size, the real mode refuses a CPU by name, and a bridge
fallback (failure_in_uda) cannot come out as exit 0. What the script
proves about the chip only a chip run can show."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, env=None, timeout=600):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          env=dict(os.environ, **(env or {})),
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_runs_end_to_end():
    r = _run("--rehearse-cpu", "--seed", "5")
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the result; a rehearsal's names the CPU
    assert json.loads(lines[-1]) == {"ok": True,
                                     "device": {"platform": "cpu"}}
    smoke = json.loads(lines[-2])["smoke"]
    assert smoke["mode"] == "rehearsal" and smoke["seed"] == 5
    for phase in ("a_cold", "a_warm"):
        assert smoke[phase]["byte_identical"] is True
        assert smoke[phase]["merge_records"] == smoke[phase]["records"]
        assert "hbm" not in smoke[phase]      # no device observation
    verdicts = smoke["b"]["verdicts"]
    assert smoke["b"]["interpret"] is True
    assert all(v["ok"] for v in verdicts.values()), verdicts
    assert set(verdicts) == {"sort:auto=carry@2^12", "sort:carry",
                             "sort:lanes", "sort:keys8",
                             "merge_sorted_pair"}
    assert set(smoke["c"]["runs"]) == {
        "ici:4/auto", "ici:4/lanes", "dcn:2,ici:2/auto", "dcn:2,ici:2/lanes",
        "ici:4/sampled-zipf"}
    assert smoke["c"]["default_engine"] == "carry"   # the CPU's
    assert "peak_bytes_in_use" not in smoke["c"]


def test_cpu_without_the_flag_is_refused_by_name():
    r = _run(env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "need 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout            # no result is printed


def test_bridge_fallback_fails_phase_a(tmp_path):
    # the consumer up-call raises once: the bridge reports it through
    # failure_in_uda and goes inert — product behaviour, smoke failure
    r = _run("--child", "a", "--rehearse-cpu", "--work-dir", str(tmp_path),
             env={"UDA_FAILPOINTS": "bridge.upcall=error:once"})
    assert r.returncode == 1, (r.stdout, r.stderr[-3000:])
    assert "failure_in_uda" in r.stderr and "Root cause" in r.stderr
    assert r.stdout.strip() == ""
