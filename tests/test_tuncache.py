"""Online tuning cache lifecycle (ISSUE 13): a probe persists winners,
a second instance serves them without re-probing, corrupt / truncated /
version-bumped files are ignored (counted, never fatal), an explicit
flag beats the cache, and a cold cache is the built-in defaults."""

import json
import sys
import time

import pytest

from uda_tpu.utils import tuncache
from uda_tpu.utils.config import Config
from uda_tpu.utils.metrics import metrics
from uda_tpu.utils.tuncache import TuneCache


@pytest.fixture()
def cache_at(tmp_path, monkeypatch):
    """A fresh cache file wired in as the process-default instance."""
    path = str(tmp_path / "tune.json")
    cache = TuneCache(path)
    monkeypatch.setattr(tuncache, "tune_cache", cache)
    return cache


# -- record/lookup round trip -------------------------------------------------


def test_record_lookup_round_trip(cache_at):
    cache_at.record("io.read", "linux", {"batch": "on"}, metric=1.25,
                    probe="t")
    rec = cache_at.lookup("io.read", "linux")
    assert rec["winner"] == {"batch": "on"}
    assert rec["metric"] == 1.25
    assert cache_at.age_s("io.read", "linux") < 60
    assert cache_at.lookup("io.read", "nope") is None
    assert metrics.get("tune.cache.hits", domain="io.read") == 1
    assert metrics.get("tune.cache.misses", domain="io.read") == 1


def test_second_instance_reads_persisted_winner(cache_at):
    """The 'second process' shape in-process: a brand-new TuneCache on
    the same path (fresh mtime state) serves the persisted winner."""
    cache_at.record("io.read", "linux", {"batch": "on", "gap_kb": 64})
    second = TuneCache(cache_at.path)
    rec = second.lookup("io.read", "linux")
    assert rec["winner"]["gap_kb"] == 64


def test_concurrent_domains_merge_not_clobber(cache_at):
    cache_at.record("io.read", "k1", {"batch": "off"})
    other = TuneCache(cache_at.path)
    other.record("other.domain", "k2", {"batch": "on"})
    assert cache_at.lookup("io.read", "k1") is not None
    assert cache_at.lookup("other.domain", "k2") is not None


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
    assert cache_at.lookup("io.read", "anything") is None
    assert metrics.get("tune.cache.invalid") >= 1


def test_invalid_entries_filtered_not_fatal(cache_at):
    with open(cache_at.path, "w") as f:
        json.dump({"schema": 1, "entries": {
            "io.read|good": {"winner": {"batch": "on"}},
            "io.read|bad": "not-a-record",
        }}, f)
    assert cache_at.lookup("io.read", "good") is not None
    assert cache_at.lookup("io.read", "bad") is None


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


def test_configured_path_beats_env_path(tmp_path, cache_at, monkeypatch):
    """An explicitly configured uda.tpu.tune.cache.path is the table the
    engine reads, whatever UDA_TPU_TUNE_CACHE (the process default,
    ``cache_at`` here) holds; with no path configured the process
    default serves."""
    monkeypatch.setenv("UDA_TPU_TUNE_CACHE", cache_at.path)
    cache_at.record("io.read", sys.platform, {"batch_max": 32})
    other = str(tmp_path / "other_tune.json")
    TuneCache(other).record("io.read", sys.platform, {"batch_max": 48})
    engine = _engine_with_cache(tmp_path, other)
    try:
        assert engine.batch_max == 48
    finally:
        engine.stop()
    from tests.test_iobatch import SyntheticResolver
    from uda_tpu.mofserver.data_engine import DataEngine

    engine = DataEngine(SyntheticResolver(str(tmp_path / "f.mof"), 4096),
                        Config())
    try:
        assert engine.batch_max == 32
    finally:
        engine.stop()


def test_stale_sort_engine_record_is_inert(tmp_path, cache_at):
    """A cache file written by a deployment that still had the
    sort.engine domain loads, serves io.read, and moves no sort: the
    engine policy reads no cache."""
    import jax

    from uda_tpu.ops import sort as sort_ops

    backend = jax.default_backend()
    with open(cache_at.path, "w") as f:
        json.dump({"schema": 1, "entries": {
            f"sort.engine|{backend}|rows17|lanes1": {
                "winner": {"engine": "gather"}, "metric": 2.0,
                "probed_unix": 1.0, "probe": "tune_probe"},
            f"sort.engine|{backend}|rows17|lanes0": {
                "winner": {"engine": "keys8"}, "metric": 2.0,
                "probed_unix": 1.0, "probe": "tune_probe"},
            f"io.read|{sys.platform}": {
                "winner": {"batch": "off", "batch_max": 32},
                "metric": 9.0, "probed_unix": 1.0, "probe": "tune_probe"},
        }}, f)
    engine = _engine_with_cache(tmp_path, cache_at.path)
    try:
        assert engine.batch_enabled is False and engine.batch_max == 32
    finally:
        engine.stop()
    assert metrics.get("tune.cache.invalid") == 0
    assert sort_ops.resolve_sort_path("auto") == "carry"   # the CPU's
    assert metrics.get("tune.cache.hits", domain="sort.engine") == 0
    assert metrics.get("tune.cache.misses", domain="sort.engine") == 0


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
    monkeypatch.setitem(tuncache._PROBES, "io.read",
                        lambda key: calls.append(key))
    cache_at.record("io.read", "k", {"batch": "on"})
    # fresh: no re-probe
    tuncache.ensure_fresh(cache_at, "io.read", "k", 3600.0)
    assert not calls
    # absent: no re-probe either (first measurement is the probe
    # script's job, never the routing hot path's)
    tuncache.ensure_fresh(cache_at, "io.read", "absent", 0.001)
    # stale: the background thread re-measures
    with open(cache_at.path) as f:
        doc = json.load(f)
    doc["entries"]["io.read|k"]["probed_unix"] = time.time() - 999
    with open(cache_at.path, "w") as f:
        json.dump(doc, f)
    tuncache.ensure_fresh(cache_at, "io.read", "k", 1.0)
    deadline = time.monotonic() + 5.0
    while not calls and time.monotonic() < deadline:
        time.sleep(0.01)
    assert calls == ["k"]
    assert metrics.get("tune.reprobes") == 1
    # disabled horizon (0): never
    calls.clear()
    tuncache.ensure_fresh(cache_at, "io.read", "k", 0.0)
    time.sleep(0.05)
    assert not calls
