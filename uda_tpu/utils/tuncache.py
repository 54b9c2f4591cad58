"""Online tuning cache: persisted per-(platform, backend) probe
winners that make tuned parameters self-service.

A small persisted winner table (the Exoshuffle posture,
arXiv:2203.05072 — shuffle policy should adapt per-workload, not be
baked in):

- **written** by seeded probes (``scripts/tune_probe.py``; any
  in-process probe can call :meth:`TuneCache.record` too),
- **consulted** by the batched host-I/O plane
  (``mofserver/data_engine.py``: batch on/off, coalesce gap, backend
  rung),
- **refreshed** by a background re-probe rung: entries older than
  ``uda.tpu.tune.reprobe.s`` are re-measured by a registered probe on
  a daemon thread (:func:`ensure_fresh`) or by
  ``tune_probe.py --reprobe-age``.

Precedence is strict and tested: **explicit config value > cached
winner > built-in default**. A cold cache is byte-for-byte today's
defaults; a corrupt, truncated or version-bumped cache file is ignored
(counted ``tune.cache.invalid``), never fatal — losing the cache must
only ever cost performance, not a job.

File format (JSON, atomic tmp+rename writes)::

    {"schema": 1, "entries": {
        "<domain>|<key>": {"winner": {...}, "metric": <float|null>,
                           "probed_unix": <float>, "probe": "<name>"}}}

``domain`` names the consumer contract (``io.read``); ``key`` encodes
the platform coordinates the consumer can cheaply reproduce at lookup
time (``sys.platform``). ``winner`` is an opaque dict the consumer
validates — a cache can never force an invalid knob value onto a
caller (validation failures count as misses). Entries of a domain no
consumer reads (a file written by an older deployment) load and are
never looked up.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

from uda_tpu.utils.logging import get_logger
from uda_tpu.utils.metrics import metrics

__all__ = ["TuneCache", "tune_cache", "cache_path_from_env",
           "register_probe", "ensure_fresh", "SCHEMA_VERSION"]

log = get_logger()

SCHEMA_VERSION = 1


def cache_path_from_env() -> str:
    """The process-default cache location: UDA_TPU_TUNE_CACHE (the
    ``uda.tpu.tune.cache.path`` config key wins where a Config is in
    hand — consumers pass the resolved path in). Empty = no cache."""
    return os.environ.get("UDA_TPU_TUNE_CACHE", "").strip()


class TuneCache:
    """One winner table bound to one file path (``path=''`` = a purely
    in-memory table: lookups miss until something records).

    Reads are cached per (path, mtime): a lookup is a dict access, not
    a file parse — the file is re-read only when another process
    replaced it."""

    def __init__(self, path: str = ""):
        self.path = path or ""
        self._mu = threading.Lock()
        self._entries: Dict[str, dict] = {}
        self._loaded_mtime: Optional[float] = None
        self._invalid_warned = False

    # -- persistence ---------------------------------------------------------

    def _load_locked(self) -> None:
        """Refresh the in-memory table from the file when it changed.
        Every failure mode — missing file, torn JSON, wrong schema,
        non-dict entries — degrades to an empty table (built-in
        defaults), counted once per observation, never raised."""
        if not self.path:
            return
        try:
            mtime = os.stat(self.path).st_mtime
        except OSError:
            self._entries = {}
            self._loaded_mtime = None
            return
        if mtime == self._loaded_mtime:
            return
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict) \
                    or doc.get("schema") != SCHEMA_VERSION \
                    or not isinstance(doc.get("entries"), dict):
                raise ValueError(
                    f"schema {doc.get('schema') if isinstance(doc, dict) else '?'}"
                    f" != {SCHEMA_VERSION} or malformed shape")
            entries = {k: v for k, v in doc["entries"].items()
                       if isinstance(v, dict) and "winner" in v}
        except (OSError, ValueError) as e:
            metrics.add("tune.cache.invalid")
            if not self._invalid_warned:
                self._invalid_warned = True
                log.warn(f"tune cache {self.path} ignored ({e}); "
                         f"using built-in defaults")
            self._entries = {}
            self._loaded_mtime = mtime  # don't re-parse a bad file per lookup
            return
        self._entries = entries
        self._loaded_mtime = mtime

    def _save_locked(self) -> None:
        if not self.path:
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump({"schema": SCHEMA_VERSION,
                           "entries": self._entries}, f, indent=1,
                          sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
            try:
                self._loaded_mtime = os.stat(self.path).st_mtime
            except OSError:
                self._loaded_mtime = None
            metrics.add("tune.cache.writes")
        except OSError as e:
            # a read-only dir / full disk must not fail the probe (or
            # the job that ran it): the winner just isn't persisted
            metrics.add("errors.swallowed")
            log.warn(f"tune cache {self.path} not persisted ({e})")
            try:
                os.unlink(tmp)
            except OSError:
                pass

    # -- the consumer surface ------------------------------------------------

    def lookup(self, domain: str, key: str) -> Optional[dict]:
        """The persisted winner record for ``domain|key``, or None
        (cold cache / unreadable file / no such entry). Counts
        ``tune.cache.hits``/``tune.cache.misses`` labeled by domain —
        the lifecycle tests key on these."""
        with self._mu:
            self._load_locked()
            rec = self._entries.get(f"{domain}|{key}")
        if rec is None:
            metrics.add("tune.cache.misses", domain=domain)
            return None
        metrics.add("tune.cache.hits", domain=domain)
        return dict(rec)

    def record(self, domain: str, key: str, winner: dict,
               metric: Optional[float] = None,
               probe: str = "") -> None:
        """Persist one probe winner (atomic write; merges with the
        entries already on disk so concurrent probes of different
        domains don't clobber each other)."""
        rec = {"winner": dict(winner), "metric": metric,
               "probed_unix": time.time(), "probe": probe}
        with self._mu:
            self._load_locked()
            self._entries[f"{domain}|{key}"] = rec
            self._save_locked()

    def age_s(self, domain: str, key: str) -> Optional[float]:
        """Seconds since the entry was probed; None when absent (or
        the record carries no timestamp — treated as infinitely
        stale by re-probe consumers)."""
        with self._mu:
            self._load_locked()
            rec = self._entries.get(f"{domain}|{key}")
        if rec is None:
            return None
        probed = rec.get("probed_unix")
        if not isinstance(probed, (int, float)):
            return float("inf")
        return max(0.0, time.time() - float(probed))

    def entries(self) -> Dict[str, dict]:
        """Snapshot of the table (diagnostics / tune_probe --list)."""
        with self._mu:
            self._load_locked()
            return {k: dict(v) for k, v in self._entries.items()}


# The process-default cache (UDA_TPU_TUNE_CACHE): what a consumer with
# no ``uda.tpu.tune.cache.path`` configured consults.
tune_cache = TuneCache(cache_path_from_env())


# -- background re-probe rung -------------------------------------------------
# A consumer that wants its winner tracked against hardware drift
# registers a probe callable; ensure_fresh() then re-measures a stale
# entry on a single daemon thread (at most one re-probe in flight per
# process — a consumer's hot path must never block on a probe).

_PROBES: Dict[str, Callable[[str], None]] = {}
_REPROBE_MU = threading.Lock()
_REPROBE_ACTIVE = False


def register_probe(domain: str, fn: Callable[[str], None]) -> None:
    """Register the re-probe implementation for ``domain``: called as
    ``fn(key)`` on the background thread; it should measure and
    ``record()`` the fresh winner."""
    _PROBES[domain] = fn


def ensure_fresh(cache: TuneCache, domain: str, key: str,
                 max_age_s: float) -> None:
    """Kick a background re-probe when the entry exists but is older
    than ``max_age_s`` (0/negative = never re-probe). Non-blocking;
    the CURRENT lookup keeps the stale winner — the refreshed one
    lands for later consumers."""
    global _REPROBE_ACTIVE
    if max_age_s <= 0:
        return
    fn = _PROBES.get(domain)
    if fn is None:
        return
    age = cache.age_s(domain, key)
    if age is None or age <= max_age_s:
        return
    with _REPROBE_MU:
        if _REPROBE_ACTIVE:
            return
        _REPROBE_ACTIVE = True

    def _run() -> None:
        global _REPROBE_ACTIVE
        try:
            metrics.add("tune.reprobes")
            fn(key)
        except Exception as e:  # noqa: BLE001 - a failed re-probe must
            # never surface into the consulting caller; the stale winner
            # keeps serving
            metrics.add("errors.swallowed")
            log.warn(f"tune re-probe of {domain}|{key} failed: {e}")
        finally:
            with _REPROBE_MU:
                _REPROBE_ACTIVE = False

    threading.Thread(target=_run, daemon=True,
                     name="uda-tune-reprobe").start()
