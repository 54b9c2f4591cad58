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


def _lexsorted(words):
    return words[np.lexsort((words[:, 2], words[:, 1], words[:, 0]))]


@pytest.mark.parametrize("path", ["carry", "lanes", "keys8"])
def test_single_chip_sort_engine_matches_lexsort(path):
    # each engine against the host oracle (np.lexsort is stable, so
    # equal keys must keep arrival order) — with a non-power-of-two n
    # (padding engages), duplicate keys, and records whose keys are all
    # 0xFFFFFFFF (they TIE with the padding lanes' +inf keys; the
    # arrival tie-break must still place every real record first)
    words = np.asarray(terasort.teragen(jax.random.key(21), 1000)).copy()
    words[5:8, :3] = 0xFFFFFFFF
    words[100:200, :3] = words[300:400, :3]
    got = np.asarray(terasort.single_chip_sort(words, path=path, tile=512,
                                               interpret=True))
    np.testing.assert_array_equal(got, _lexsorted(words))


@pytest.mark.parametrize("backend,want", [("cpu", "carry"),
                                          ("tpu", "lanes")])
def test_auto_is_one_answer_for_both_surfaces(monkeypatch, backend, want):
    # single_chip_sort and the distributed step resolve "auto" to the
    # same engine on either backend (the Pallas kernels interpreted:
    # the mesh is the CPU's whatever default_backend is made to say)
    from uda_tpu.parallel import distributed

    seen = {}

    def spy(mod, name, surface, engine_of):
        real = getattr(mod, name)

        def wrapper(*args, **kw):
            seen[surface] = engine_of(args)
            return real(*args, **kw)

        monkeypatch.setattr(mod, name, wrapper)

    spy(terasort, "_single_chip_sort", "single", lambda a: "carry")
    spy(terasort, "_single_chip_sort_lanes", "single", lambda a: a[1])
    spy(distributed, "_sort_step", "step", lambda a: a[6])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    words = np.asarray(terasort.teragen(jax.random.key(6), 4 * 128))
    out = np.asarray(terasort.single_chip_sort(words, interpret=True))
    np.testing.assert_array_equal(out, _lexsorted(words))
    res = terasort.distributed_terasort(words, make_mesh(4))
    res.check()
    shards = np.asarray(res.words).reshape(4, -1, terasort.RECORD_WORDS)
    nvalid = np.asarray(res.valid_counts).reshape(-1)
    rows = np.concatenate([shards[d, :nvalid[d]] for d in range(4)])
    np.testing.assert_array_equal(rows, _lexsorted(words))
    assert seen == {"single": want, "step": want}


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
