"""Online tuning cache lifecycle (ISSUE 13): probe persists winners, a
second/fresh process routes from the cache without re-probing,
corrupt/truncated/version-bumped files are ignored (counted, never
fatal), env-var winners beat the cache, and a cold cache is
byte-for-byte today's built-in routing."""

import json
import os
import subprocess
import sys
import time

import pytest

from uda_tpu.ops import sort as sort_ops
from uda_tpu.utils import tuncache
from uda_tpu.utils.config import Config
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.tuncache import TuneCache, rows_bucket

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sort_key(n_rows, lanes_ok=False):
    import jax

    return (f"{jax.default_backend()}|rows{rows_bucket(n_rows)}"
            f"|lanes{int(lanes_ok)}")


@pytest.fixture()
def cache_at(tmp_path, monkeypatch):
    """A fresh cache file wired in as the process-default instance
    (what route_engine consults)."""
    path = str(tmp_path / "tune.json")
    cache = TuneCache(path)
    monkeypatch.setattr(tuncache, "tune_cache", cache)
    return cache


# -- record/lookup round trip -------------------------------------------------


def test_record_lookup_round_trip(cache_at):
    cache_at.record("sort.engine", "cpu|rows16|lanes0",
                    {"engine": "gather"}, metric=1.25, probe="t")
    rec = cache_at.lookup("sort.engine", "cpu|rows16|lanes0")
    assert rec["winner"] == {"engine": "gather"}
    assert rec["metric"] == 1.25
    assert cache_at.age_s("sort.engine", "cpu|rows16|lanes0") < 60
    assert cache_at.lookup("sort.engine", "nope") is None
    assert metrics.get("tune.cache.hits", domain="sort.engine") == 1
    assert metrics.get("tune.cache.misses", domain="sort.engine") == 1


def test_second_instance_reads_persisted_winner(cache_at):
    """The 'second process' shape in-process: a brand-new TuneCache on
    the same path (fresh mtime state) serves the persisted winner."""
    cache_at.record("io.read", "linux", {"batch": "on", "gap_kb": 64})
    second = TuneCache(cache_at.path)
    rec = second.lookup("io.read", "linux")
    assert rec["winner"]["gap_kb"] == 64


def test_concurrent_domains_merge_not_clobber(cache_at):
    cache_at.record("sort.engine", "k1", {"engine": "carry"})
    other = TuneCache(cache_at.path)
    other.record("io.read", "k2", {"batch": "on"})
    assert cache_at.lookup("sort.engine", "k1") is not None
    assert cache_at.lookup("io.read", "k2") is not None


# -- invalid files: ignored, counted, never fatal -----------------------------


@pytest.mark.parametrize("content", [
    "{ not json at all",                                   # torn JSON
    json.dumps({"schema": 999, "entries": {}}),            # version bump
    json.dumps({"schema": 1, "entries": "not-a-dict"}),    # malformed
    "",                                                    # truncated
])
def test_invalid_cache_ignored_and_counted(cache_at, content):
    with open(cache_at.path, "w") as f:
        f.write(content)
    assert cache_at.lookup("sort.engine", "anything") is None
    assert metrics.get("tune.cache.invalid") >= 1
    # routing still works on the defaults
    assert sort_ops.route_engine(1 << 16, "auto") \
        == sort_ops.resolve_sort_path("auto")


def test_invalid_entries_filtered_not_fatal(cache_at):
    with open(cache_at.path, "w") as f:
        json.dump({"schema": 1, "entries": {
            "sort.engine|good": {"winner": {"engine": "gather"}},
            "sort.engine|bad": "not-a-record",
        }}, f)
    assert cache_at.lookup("sort.engine", "good") is not None
    assert cache_at.lookup("sort.engine", "bad") is None


# -- route_engine integration -------------------------------------------------


def test_cold_cache_routes_exactly_todays_defaults(cache_at,
                                                   monkeypatch):
    monkeypatch.setattr(sort_ops, "DEPLOYED_SORT_PATH", "")
    for n in (1, 1 << 10, 1 << 16, 1 << 20, 1 << 22):
        for lanes_ok in (False, True):
            assert sort_ops.route_engine(n, "auto", lanes_ok) == \
                sort_ops.resolve_sort_path("auto", lanes_ok)
    # explicit paths bypass the cache entirely
    assert sort_ops.route_engine(1 << 16, "gather") == "gather"


def test_route_engine_consults_cached_winner(cache_at, monkeypatch):
    monkeypatch.setattr(sort_ops, "DEPLOYED_SORT_PATH", "")
    n = 1 << 16
    cache_at.record("sort.engine", _sort_key(n),
                    {"engine": "gather2"}, metric=2.0)
    assert sort_ops.route_engine(n, "auto") == "gather2"
    assert metrics.get("tune.cache.hits", domain="sort.engine") >= 1
    # a different size class misses the cache -> built-in default
    assert sort_ops.route_engine(1 << 22, "auto") == \
        sort_ops.resolve_sort_path("auto")


def test_env_winner_beats_cache(cache_at, monkeypatch):
    n = 1 << 16
    cache_at.record("sort.engine", _sort_key(n),
                    {"engine": "gather2"})
    monkeypatch.setattr(sort_ops, "DEPLOYED_SORT_PATH", "carrychunk")
    assert sort_ops.route_engine(n, "auto") == "carrychunk"


def test_invalid_cached_engine_ignored(cache_at, monkeypatch):
    monkeypatch.setattr(sort_ops, "DEPLOYED_SORT_PATH", "")
    n = 1 << 16
    cache_at.record("sort.engine", _sort_key(n, lanes_ok=False),
                    {"engine": "totally-made-up"})
    assert sort_ops.route_engine(n, "auto") == \
        sort_ops.resolve_sort_path("auto")
    # a lanes winner cached for a lanes-capable key must not leak to a
    # lanes-incapable caller (validation per lookup, not per file)
    cache_at.record("sort.engine", _sort_key(n, lanes_ok=False),
                    {"engine": "lanes"})
    assert sort_ops.route_engine(n, "auto", lanes_ok=False) == \
        sort_ops.resolve_sort_path("auto", lanes_ok=False)


def test_fresh_process_routes_from_cache_without_probe(cache_at):
    """THE acceptance round trip: a persisted winner is consulted by
    route_engine in a FRESH interpreter — cache hit recorded, probe
    counter ZERO (nothing re-measures on the routing path)."""
    n = 1 << 16
    # the fresh process is CPU-backend (env below): key accordingly
    key = f"cpu|rows{rows_bucket(n)}|lanes0"
    cache_at.record("sort.engine", key, {"engine": "gather2"},
                    metric=9.9, probe="lifecycle-test")
    code = (
        "import os\n"
        "from uda_tpu.ops import sort as sort_ops\n"
        "from uda_tpu.utils.metrics import metrics\n"
        f"engine = sort_ops.route_engine({n}, 'auto')\n"
        "print('ENGINE', engine)\n"
        "print('PROBES', int(metrics.get('tune.probes')))\n"
        "print('HITS', int(metrics.get('tune.cache.hits')))\n"
    )
    env = dict(os.environ)
    env.pop("UDA_TPU_SORT_PATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["UDA_TPU_TUNE_CACHE"] = cache_at.path
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert "ENGINE gather2" in out.stdout
    assert "PROBES 0" in out.stdout
    assert "HITS 1" in out.stdout


# -- the io.read consumer -----------------------------------------------------


def _engine_with_cache(tmp_path, cache_path, overrides=None):
    from tests.test_iobatch import SyntheticResolver, _write

    path = _write(str(tmp_path), "f.mof", 4096)
    cfg = {"uda.tpu.tune.cache.path": cache_path}
    cfg.update(overrides or {})
    from uda_tpu.mofserver.data_engine import DataEngine

    return DataEngine(SyntheticResolver(path, 4096), Config(cfg))


def test_io_plane_consults_cache_winner(tmp_path, cache_at):
    cache_at.record("io.read", sys.platform,
                    {"batch": "off", "gap_kb": 256, "batch_max": 32,
                     "backend": "pread"})
    engine = _engine_with_cache(tmp_path, cache_at.path)
    try:
        assert engine.batch_enabled is False
        assert engine.coalesce_gap_bytes == 256 << 10
        assert engine.batch_max == 32
        assert engine.io_backend == "pread"
    finally:
        engine.stop()


def test_io_plane_explicit_config_beats_cache(tmp_path, cache_at):
    cache_at.record("io.read", sys.platform,
                    {"batch": "off", "gap_kb": 256})
    engine = _engine_with_cache(
        tmp_path, cache_at.path,
        {"uda.tpu.read.batch": "on",
         "uda.tpu.read.coalesce.gap.kb": 8})
    try:
        assert engine.batch_enabled is True
        assert engine.coalesce_gap_bytes == 8 << 10
    finally:
        engine.stop()


def test_config_path_installs_process_default(tmp_path, cache_at,
                                              monkeypatch):
    """An explicitly-configured uda.tpu.tune.cache.path must reach
    route_engine too (which has no Config in scope): constructing the
    engine installs the path as the process default — unless the env
    var is set, which always wins."""
    monkeypatch.setattr(sort_ops, "DEPLOYED_SORT_PATH", "")
    other = str(tmp_path / "other_tune.json")
    TuneCache(other).record("sort.engine", _sort_key(1 << 16),
                            {"engine": "gather2"})
    monkeypatch.delenv("UDA_TPU_TUNE_CACHE", raising=False)
    engine = _engine_with_cache(tmp_path, other)
    try:
        assert tuncache.tune_cache.path == other
        assert sort_ops.route_engine(1 << 16, "auto") == "gather2"
    finally:
        engine.stop()
    # with the env channel set, config must NOT displace it
    monkeypatch.setenv("UDA_TPU_TUNE_CACHE", cache_at.path)
    before = tuncache.tune_cache
    engine = _engine_with_cache(tmp_path, str(tmp_path / "third.json"))
    try:
        assert tuncache.tune_cache is before
    finally:
        engine.stop()


def test_io_plane_invalid_winner_values_ignored(tmp_path, cache_at):
    cache_at.record("io.read", sys.platform,
                    {"batch": "maybe", "gap_kb": "lots",
                     "batch_max": -3, "backend": "carrier-pigeon"})
    engine = _engine_with_cache(tmp_path, cache_at.path)
    try:
        assert engine.batch_enabled is True           # default on
        assert engine.coalesce_gap_bytes == 64 << 10  # flag default
        assert engine.batch_max == 256                # flag default
        assert engine.io_backend in ("io_uring", "preadv", "pread")
    finally:
        engine.stop()


# -- background re-probe rung -------------------------------------------------


def test_ensure_fresh_reprobes_stale_entry(cache_at, monkeypatch):
    calls = []
    monkeypatch.setitem(tuncache._PROBES, "sort.engine",
                        lambda key: calls.append(key))
    cache_at.record("sort.engine", "k", {"engine": "carry"})
    # fresh: no re-probe
    tuncache.ensure_fresh(cache_at, "sort.engine", "k", 3600.0)
    assert not calls
    # absent: no re-probe either (first measurement is the probe
    # script's job, never the routing hot path's)
    tuncache.ensure_fresh(cache_at, "sort.engine", "absent", 0.001)
    # stale: the background thread re-measures
    with open(cache_at.path) as f:
        doc = json.load(f)
    doc["entries"]["sort.engine|k"]["probed_unix"] = time.time() - 999
    with open(cache_at.path, "w") as f:
        json.dump(doc, f)
    tuncache.ensure_fresh(cache_at, "sort.engine", "k", 1.0)
    deadline = time.monotonic() + 5.0
    while not calls and time.monotonic() < deadline:
        time.sleep(0.01)
    assert calls == ["k"]
    assert metrics.get("tune.reprobes") == 1
    # disabled horizon (0): never
    calls.clear()
    tuncache.ensure_fresh(cache_at, "sort.engine", "k", 0.0)
    time.sleep(0.05)
    assert not calls
