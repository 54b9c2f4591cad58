"""TeraSort workload: single-chip and distributed (BASELINE configs 2/5)."""

import jax
import numpy as np
import pytest

from uda_tpu.models import terasort
from uda_tpu.parallel.mesh import make_mesh


def test_teragen_shape_and_pad():
    words = np.asarray(terasort.teragen(jax.random.key(0), 1024))
    assert words.shape == (1024, terasort.RECORD_WORDS)
    assert words.dtype == np.uint32
    # key pad bytes are zero (fixed-width memcmp contract)
    assert (words[:, 2] & 0xFFFF).max() == 0


def test_single_chip_sort_total_order():
    words = np.asarray(terasort.teragen(jax.random.key(1), 4096))
    out = np.asarray(terasort.single_chip_sort(words))
    keys = [tuple(r[:3]) for r in out]
    assert keys == sorted(keys)
    assert sorted(map(tuple, out)) == sorted(map(tuple, words))
    terasort.validate_sorted(out, words)


def test_single_chip_sort_gather_path_matches_carry():
    # the bounded-compile accelerator path must produce byte-identical
    # output to the operand-carry path (stability included: duplicate
    # keys keep arrival order in both)
    words = np.asarray(terasort.teragen(jax.random.key(7), 2048)).copy()
    words[100:300, :3] = words[700:900, :3]  # inject duplicate keys
    a = np.asarray(terasort.single_chip_sort(words, path="carry"))
    b = np.asarray(terasort.single_chip_sort(words, path="gather"))
    np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_single_chip_sort_all_engines_match_carry():
    # every public engine, byte-identical to the carry oracle — with a
    # non-power-of-two n (padding engages), duplicate keys (stability),
    # and records whose keys are all 0xFFFFFFFF (they TIE with the
    # padding lanes' +inf keys; the arrival tie-break must still place
    # every real record before the padding)
    words = np.asarray(terasort.teragen(jax.random.key(21), 1000)).copy()
    words[5:8, :3] = 0xFFFFFFFF
    words[100:200, :3] = words[300:400, :3]
    a = np.asarray(terasort.single_chip_sort(words, path="carry"))
    for path in ("lanes", "lanes2", "keys8", "keys8f", "gather",
                 "gather2", "carrychunk"):
        b = np.asarray(terasort.single_chip_sort(words, path=path,
                                                 tile=512, interpret=True))
        np.testing.assert_array_equal(a, b, err_msg=path)


def test_bench_step_both_paths_validate():
    for path in ("carry", "gather"):
        viol, ck_in, ck_out = terasort.bench_step(
            jax.random.key(5), 4096, 2, path=path)
        assert int(viol) == 0, path
        assert np.uint32(ck_in) == np.uint32(ck_out), path


def test_teragen_lanes_matches_layout():
    from uda_tpu.ops.pallas_sort import ROWS

    x = np.asarray(terasort.teragen_lanes(jax.random.key(9), 512))
    assert x.shape == (ROWS, 512)
    assert (x[2] & 0xFFFF).max() == 0          # key pad bytes zero
    assert x[terasort.RECORD_WORDS:].max() == 0  # layout pad rows zero


@pytest.mark.slow
def test_bench_step_lanes_path_validates():
    # interpret=True: Pallas kernels run on the CPU test backend
    viol, ck_in, ck_out = terasort.bench_step(
        jax.random.key(5), 2048, 2, path="lanes", tile=512, interpret=True)
    assert int(viol) == 0
    assert np.uint32(ck_in) == np.uint32(ck_out)


@pytest.mark.slow
def test_bench_step_keys8_path_validates():
    for path in ("keys8", "keys8f"):
        viol, ck_in, ck_out = terasort.bench_step(
            jax.random.key(5), 2048, 2, path=path, tile=512,
            interpret=True)
        assert int(viol) == 0, path
        assert np.uint32(ck_in) == np.uint32(ck_out), path


def test_bench_step_gather2_path_validates():
    viol, ck_in, ck_out = terasort.bench_step(
        jax.random.key(5), 2048, 2, path="gather2", tile=512)
    assert int(viol) == 0
    assert np.uint32(ck_in) == np.uint32(ck_out)


def test_bench_step_carrychunk_path_validates():
    viol, ck_in, ck_out = terasort.bench_step(
        jax.random.key(5), 2048, 2, path="carrychunk", tile=512)
    assert int(viol) == 0
    assert np.uint32(ck_in) == np.uint32(ck_out)


@pytest.mark.slow
def test_sort_lanes_keys8_matches_sort_lanes():
    # the keys8 engine (keys-only cascade + one global payload gather)
    # must be byte-identical to the 32-row pipeline, stability included,
    # in both the standard and folded cascade variants
    from uda_tpu.ops import pallas_sort

    x = np.asarray(terasort.teragen_lanes(jax.random.key(12), 2048)).copy()
    x[:3, 100:300] = x[:3, 700:900]  # duplicate keys
    a = np.asarray(pallas_sort.sort_lanes(x, num_keys=terasort.KEY_WORDS,
                                          tile=512, interpret=True))
    for folded in (False, True):
        b = np.asarray(terasort.sort_lanes_keys8(x, tile=512,
                                                 interpret=True,
                                                 folded=folded))
        np.testing.assert_array_equal(a, b, err_msg=f"folded={folded}")


@pytest.mark.slow
def test_bench_step_lanes_checksum_matches_oracle():
    # the lanes checksum must use the same per-column multipliers as the
    # SoA paths: a sorted output altered by a column swap fails
    import jax.numpy as jnp

    from uda_tpu.ops import pallas_sort

    x = terasort.teragen_lanes(jax.random.key(11), 1024)
    out = pallas_sort.sort_lanes(x, num_keys=terasort.KEY_WORDS, tile=512,
                                 interpret=True)
    got = np.asarray(pallas_sort.lanes_to_rows(out, terasort.RECORD_WORDS))
    rows = np.asarray(pallas_sort.lanes_to_rows(x, terasort.RECORD_WORDS))
    terasort.validate_sorted(got, rows)


def test_distributed_terasort_gather_payload_path():
    from uda_tpu.parallel.distributed import (distributed_sort_step,
                                              uniform_splitters)

    mesh = make_mesh(4)
    words = np.asarray(terasort.teragen(jax.random.key(6), 4 * 256))
    res = distributed_sort_step(words, uniform_splitters(4), mesh,
                                "shuffle", capacity=256, num_keys=3,
                                payload_path="gather")
    res.check()
    out = np.asarray(res.words).reshape(4, -1, terasort.RECORD_WORDS)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    rows = np.concatenate([out[d, :nvalid[d]] for d in range(4)])
    terasort.validate_sorted(rows, words)


def test_validate_sorted_catches_violation():
    words = np.asarray(terasort.teragen(jax.random.key(2), 256))
    out = np.asarray(terasort.single_chip_sort(words))
    bad = out[::-1].copy()
    with pytest.raises(AssertionError):
        terasort.validate_sorted(bad)


def test_validate_sorted_catches_corruption():
    words = np.asarray(terasort.teragen(jax.random.key(3), 256))
    out = np.asarray(terasort.single_chip_sort(words)).copy()
    out[10, 5] ^= 1  # flip one payload bit
    with pytest.raises(AssertionError):
        terasort.validate_sorted(out, words)


def test_validate_sorted_catches_column_swap():
    # distinct per-column multipliers in the checksum: swapping two
    # value columns in every row (a plausible gather-path indexing bug)
    # must fail even though row sums with a single multiplier would not
    words = np.asarray(terasort.teragen(jax.random.key(8), 256))
    out = np.asarray(terasort.single_chip_sort(words)).copy()
    out[:, [5, 7]] = out[:, [7, 5]]
    with pytest.raises(AssertionError):
        terasort.validate_sorted(out, words)


def test_distributed_terasort_8dev():
    mesh = make_mesh(8)
    words = np.asarray(terasort.teragen(jax.random.key(4), 8 * 256))
    res = terasort.distributed_terasort(words, mesh)
    res.check()
    out = np.asarray(res.words).reshape(8, -1, terasort.RECORD_WORDS)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    rows = np.concatenate([out[d, :nvalid[d]] for d in range(8)])
    assert rows.shape[0] == words.shape[0]
    keys = [tuple(r[:3]) for r in rows]
    assert keys == sorted(keys)
    terasort.validate_sorted(rows, words)


@pytest.mark.slow
def test_graft_entry_contract():
    """The driver's contract: a FRESH process can jit entry() and run
    dryrun_multichip on a virtual CPU mesh. Exercised in a subprocess
    because that is exactly how the driver consumes __graft_entry__ —
    and because the dryrun's dozen large 8-device XLA CPU compiles
    proved crash-flaky when run in-process late in the full suite
    (segfault inside backend_compile_and_load at this exact test,
    2026-07-31; not reproducible in isolation or in any half-suite
    subset, and MALLOC_CHECK_/ASan full-suite runs found no native
    heap misuse — notes in git history). A fresh interpreter is both
    the honest contract and the stable one."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = (
        "import jax, __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "assert out.shape == args[0].shape\n"
        "g.dryrun_multichip(8)\n"
        "g.dryrun_multichip(4)\n"
        "print('GRAFT_CONTRACT_OK')\n"
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                   " --xla_force_host_platform_device_count=8").strip(),
    )
    r = subprocess.run([sys.executable, "-c", prog], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"graft entry contract failed:\n{r.stdout}\n{r.stderr}"
    assert "GRAFT_CONTRACT_OK" in r.stdout
