"""Native embedding shim (libuda_tpu_bridge.so): the C-ABI analogue of
the reference's JNI bridge, driven by a standalone C++ embedder — the
role of the reference's JNI mechanism tests (reference tests/jni*/README:
callback registration, DirectByteBuffer-style data hand-off, command
dispatch), but asserting the FULL reduce flow end-to-end."""

import os
import shutil
import subprocess

import pytest

from tests.helpers import make_mof_tree

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "uda_tpu", "native")


def _build() -> str:
    # toolchain presence is handled by pytestmark; with a toolchain, a
    # failing build is a regression, not a skip
    exe = os.path.join(NATIVE_DIR, "bridge_shim_test")
    r = subprocess.run(["make", "-C", NATIVE_DIR, "shim"],
                       capture_output=True, text=True, check=False)
    assert r.returncode == 0 and os.path.exists(exe), \
        f"bridge shim build failed: {r.stderr[-800:]}"
    return exe


pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("python3-config") is None,
    reason="no native toolchain")


def _run(exe, root, job, num_maps, reduce_id, upcall=False):
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(NATIVE_DIR))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # the embedded interpreter must target CPU in tests, whatever
    # backend is ambient
    env["UDA_TPU_PY_BOOTSTRAP"] = (
        'import jax; jax.config.update("jax_platforms", "cpu")')
    return subprocess.run(
        [exe, root, job, str(num_maps), str(reduce_id)] +
        (["upcall"] if upcall else []),
        capture_output=True, text=True, timeout=120, env=env, check=False)


def test_shim_full_reduce_flow(tmp_path):
    exe = _build()
    expected = make_mof_tree(str(tmp_path), "job_shim", 3, 2, 30, seed=7)
    for r in (0, 1):
        proc = _run(exe, str(tmp_path), "job_shim", 3, r)
        assert proc.returncode == 0, (proc.stdout, proc.stderr[-800:])
        out = proc.stdout.strip().split()
        assert out[0] == "MERGED" and out[2] == "RECORDS"
        assert int(out[3]) == len(expected[r])


def test_shim_get_path_uda_upcall_resolution(tmp_path):
    # no local dir in INIT: every first fetch resolves through the C
    # get_path_uda callback (index triples parsed by the embedder),
    # covering the C->Python IndexRecord marshalling
    exe = _build()
    expected = make_mof_tree(str(tmp_path), "job_up", 3, 2, 25, seed=9)
    proc = _run(exe, str(tmp_path), "job_up", 3, 1, upcall=True)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-800:])
    assert int(proc.stdout.strip().split()[3]) == len(expected[1])


def test_shim_missing_job_signals_failure(tmp_path):
    exe = _build()
    # no MOF tree: the fetch fails inside the engine; the shim must
    # surface it through failure_in_uda (exit code 8 in the driver),
    # not hang or crash
    proc = _run(exe, str(tmp_path), "job_absent", 2, 0)
    assert proc.returncode == 8, (proc.returncode, proc.stderr[-500:])
