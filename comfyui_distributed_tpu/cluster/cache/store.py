"""Size-capped LRU cache tier with pinning and checksummed persistence.

One :class:`CacheTier` instance per tier (conditioning / result). The
policy mirrors ``cluster/residency.ResidencyPlanner`` — least-recently-
used eviction under a byte budget, pinned entries untouchable — applied
to named numpy-array bundles instead of model bundles.

Persistence follows the ``utils/jsonio`` contract the shape catalog
established, extended with a binary sidecar per entry:

- the **index** (``<tier>_index.json``) is read-merge-atomic-written, so
  concurrent writers (serving master, bench, a second controller against
  a shared cache dir) union instead of clobbering;
- each **entry** is one ``.npz`` sidecar written tmp+``os.replace``, its
  SHA-256 recorded in the index. A load recomputes the checksum; any
  mismatch is rejected LOUDLY (log + ``cdt_cache_corrupt_total``), the
  entry is deleted, and the caller recomputes — a flipped bit on disk
  can never become a served byte.

Entries whose arrays use non-standard dtypes (e.g. ml_dtypes bfloat16)
are kept memory-only: their ``.npz`` round-trip is not guaranteed
bit-exact across numpy versions, and bit-exactness is the whole point.
"""

from __future__ import annotations

import contextlib
import io
import os
import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import Optional

import numpy as np

from ...lint.lockorder import tracked_lock
from ...utils.jsonio import atomic_write_json, read_json
from ...utils.logging import debug_log, log
from . import keys as _keys


def _tier_metrics():
    """(enabled, metrics module) — guarded import so the store stays
    usable in processes that never initialize telemetry."""
    try:
        from ... import telemetry
        from ...telemetry import metrics as _tm

        return telemetry.enabled(), _tm
    except Exception:  # noqa: BLE001 — telemetry is never load-bearing
        return False, None


def _persistable(arrays: dict) -> bool:
    """Only standard numeric dtypes round-trip bit-exactly through
    ``.npz`` everywhere; anything else (bf16 et al.) stays memory-only."""
    return all(a.dtype.kind in "fiub" for a in arrays.values())


class _Entry:
    __slots__ = ("arrays", "nbytes", "pins")

    def __init__(self, arrays: dict, nbytes: int):
        self.arrays = arrays
        self.nbytes = nbytes
        self.pins = 0


class CacheTier:
    """Thread-safe LRU tier over ``key -> {name: np.ndarray}`` bundles.

    ``max_bytes`` caps the in-memory tier (0 disables memory caching);
    ``directory``/``disk_max_bytes`` enable the persisted tier shared
    across processes and restarts (None/0 = memory-only).
    """

    def __init__(self, tier: str, max_bytes: int,
                 directory: "Path | str | None" = None,
                 disk_max_bytes: int = 0):
        self.tier = tier
        self.max_bytes = int(max_bytes)
        self.dir = Path(directory) if directory else None
        self.disk_max_bytes = int(disk_max_bytes)
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self._lock = tracked_lock(f"cache.tier.{tier}", reentrant=True)
        self.counts = {"hit": 0, "miss": 0, "disk_hit": 0, "put": 0,
                       "evicted": 0, "corrupt": 0, "persisted": 0}

    # --- introspection ------------------------------------------------------

    @property
    def entry_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def stats(self) -> dict:
        with self._lock:
            counts = dict(self.counts)
            return {
                "entries": len(self._entries),
                "bytes": sum(e.nbytes for e in self._entries.values()),
                "max_bytes": self.max_bytes,
                "persist_dir": str(self.dir) if self.dir else None,
                **counts,
            }

    def keys(self) -> list:
        """In-memory keys, LRU-oldest first (fleet handback enumerates
        these to find the shard's hot entries)."""
        with self._lock:
            return list(self._entries)

    def peek(self, key: str) -> Optional[dict]:
        """Arrays for ``key`` from memory only — no disk consult, no LRU
        touch, no hit/miss accounting. The fleet tier's remote-serve and
        handback paths use it so a neighbor's probe doesn't distort this
        host's local hit-rate window or recency order."""
        with self._lock:
            e = self._entries.get(key)
            return dict(e.arrays) if e is not None else None

    def drop_memory(self, key: str) -> None:
        """Drop one entry from memory only (exactly-once drain handback:
        after a successful move the donor must stop serving the entry
        from its LRU, but the checksummed sidecar stays valid)."""
        with self._lock:
            self._entries.pop(key, None)
        self._export_gauges()

    # --- pinning (mirrors cluster/residency) --------------------------------

    def pin(self, key: str) -> bool:
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return False
            e.pins += 1
            return True

    def unpin(self, key: str) -> None:
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.pins > 0:
                e.pins -= 1

    # --- the cache ----------------------------------------------------------

    def get(self, key: str) -> Optional[dict]:
        """Arrays for ``key``, or None. Memory first; on a memory miss the
        persisted tier is consulted (checksum-verified) and a hit is
        promoted into memory."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                self._count("hit")
                return dict(e.arrays)
        arrays = self._disk_get(key)
        if arrays is not None:
            self._count("disk_hit")
            self._insert(key, arrays, persist=False)
            return dict(arrays)
        self._count("miss")
        return None

    def put(self, key: str, arrays: dict, persist: bool = True) -> None:
        """Insert (or refresh) ``key``. ``persist=False`` keeps the entry
        memory-only even when a directory is configured — the degraded-
        tokenization guard and tests use it."""
        arrays = {n: np.asarray(a) for n, a in arrays.items()}
        self._insert(key, arrays, persist=persist)
        self._count("put")

    def _insert(self, key: str, arrays: dict, persist: bool) -> None:
        nbytes = sum(a.nbytes for a in arrays.values())
        with self._lock:
            old = self._entries.pop(key, None)
            if self.max_bytes > 0 or old is not None:
                self._entries[key] = _Entry(arrays, nbytes)
                if old is not None:
                    self._entries[key].pins = old.pins
                self._evict_over_budget_locked()
        if persist and self.dir is not None and _persistable(arrays):
            self._disk_put(key, arrays)
        self._export_gauges()

    def _evict_over_budget_locked(self) -> None:
        if self.max_bytes <= 0:
            return
        used = sum(e.nbytes for e in self._entries.values())
        for key in list(self._entries):
            if used <= self.max_bytes:
                return
            e = self._entries[key]
            if e.pins > 0:
                continue
            del self._entries[key]
            used -= e.nbytes
            self._count("evicted", export=True)

    # --- persistence --------------------------------------------------------

    def _index_path(self) -> Path:
        return self.dir / f"{self.tier}_index.json"

    def _entry_path(self, key: str) -> Path:
        return self.dir / self.tier / f"{key}.npz"

    @contextlib.contextmanager
    def _index_flock(self):
        """Advisory cross-PROCESS lock around the index read-merge-write
        (the in-process RLock can't serialize a second controller or a
        bench sharing CDT_CACHE_DIR — without this, two writers would
        last-write-win and the loser's row, though its sidecar is on
        disk, silently stops being servable). Degrades to lockless on
        filesystems without flock — same behavior as before, worst case
        a lost index row, never a wrong byte (entries are checksummed)."""
        try:
            import fcntl
        except ImportError:
            yield
            return
        lock_path = self.dir / f"{self.tier}_index.lock"
        try:
            fd = os.open(lock_path, os.O_CREAT | os.O_RDWR)
        except OSError:
            yield
            return
        try:
            try:
                fcntl.flock(fd, fcntl.LOCK_EX)
            except OSError:
                pass
            yield
        finally:
            os.close(fd)

    def _read_index(self) -> dict:
        """Parsed index entries, cached against the file's (mtime_ns,
        size) — a memory miss on the serving hot path must not re-parse
        a multi-thousand-row JSON per request. Writers always go through
        ``_write_index``, which re-reads under the flock."""
        path = self._index_path()
        try:
            st = path.stat()
            stamp = (st.st_mtime_ns, st.st_size)
        except OSError:
            stamp = None
        with self._lock:
            cached = getattr(self, "_index_cache", None)
            if cached is not None and cached[0] == stamp:
                return cached[1]
        entries = self._read_index_uncached()
        with self._lock:
            self._index_cache = (stamp, entries)
        return entries

    def _read_index_uncached(self) -> dict:
        data = read_json(self._index_path())
        entries = (data or {}).get("entries")
        return entries if isinstance(entries, dict) else {}

    def _write_index(self, mutate) -> None:
        """Read-merge-write under both locks (thread + process):
        ``mutate(entries)`` edits the freshly re-read mapping, so
        concurrent writers union."""
        with self._lock, self._index_flock():
            entries = self._read_index_uncached()
            mutate(entries)
            atomic_write_json(self._index_path(),
                              {"version": 1, "tier": self.tier,
                               "entries": entries})
            try:
                st = self._index_path().stat()
                self._index_cache = ((st.st_mtime_ns, st.st_size), entries)
            except OSError:
                self._index_cache = (None, entries)

    def _disk_put(self, key: str, arrays: dict) -> None:
        try:
            buf = io.BytesIO()
            np.savez(buf, **arrays)
            payload = buf.getvalue()
            path = self._entry_path(key)
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(".tmp")
            tmp.write_bytes(payload)
            os.replace(tmp, path)
            row = {"file": path.name, "sha256": _keys.checksum(payload),
                   "bytes": len(payload), "saved_at": time.time()}
            self._write_index(lambda e: e.__setitem__(key, row))
            # counts is mutated under self._lock everywhere else; a bare
            # dict += here is a lost-update race (lint rule L001)
            with self._lock:
                self.counts["persisted"] += 1
            self._disk_evict_over_budget()
        except OSError as e:
            debug_log(f"cache[{self.tier}]: persist of {key[:12]} "
                      f"failed: {e}")

    def _disk_get(self, key: str) -> Optional[dict]:
        if self.dir is None:
            return None
        row = self._read_index().get(key)
        if not isinstance(row, dict):
            return None
        path = self._entry_path(key)
        try:
            payload = path.read_bytes()
        except OSError:
            return None
        if _keys.checksum(payload) != row.get("sha256"):
            # integrity failure is LOUD and terminal for the entry: drop
            # it everywhere and let the caller recompute — a corrupted
            # sidecar must never become a served byte
            log(f"cache[{self.tier}]: CHECKSUM MISMATCH for entry "
                f"{key[:16]}… — rejecting and deleting (recompute follows)")
            self._count("corrupt", export=True)
            self.invalidate(key)
            return None
        try:
            with np.load(io.BytesIO(payload)) as z:
                return {n: z[n] for n in z.files}
        except (OSError, ValueError) as e:
            log(f"cache[{self.tier}]: unreadable entry {key[:16]}… "
                f"({e}) — deleting")
            self._count("corrupt", export=True)
            self.invalidate(key)
            return None

    def _disk_evict_over_budget(self) -> None:
        if self.disk_max_bytes <= 0:
            return
        entries = self._read_index()
        used = sum(int(r.get("bytes", 0)) for r in entries.values())
        if used <= self.disk_max_bytes:
            return
        victims = []
        for key, row in sorted(entries.items(),
                               key=lambda kv: kv[1].get("saved_at", 0.0)):
            if used <= self.disk_max_bytes:
                break
            victims.append(key)
            used -= int(row.get("bytes", 0))
        # ONE index rewrite for the whole victim set (per-victim
        # invalidate() would pay a flock + full-index read-merge-write
        # each, on the graph-exec thread that just filled the entry)
        def _drop_all(e):
            for key in victims:
                e.pop(key, None)

        self._write_index(_drop_all)
        for key in victims:
            try:
                self._entry_path(key).unlink()
            except OSError:
                pass
            self._count("evicted")
        self._export_gauges()

    def clear_memory(self) -> int:
        """Drop every in-memory entry (operator invalidation route);
        persisted entries are untouched — they are content-addressed and
        stay valid. Returns the number dropped."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
        self._export_gauges()
        return n

    def invalidate(self, key: str, memory: bool = True) -> None:
        """Drop one entry from memory and disk (corruption handling,
        operator invalidation)."""
        if memory:
            with self._lock:
                self._entries.pop(key, None)
        if self.dir is not None:
            self._write_index(lambda e: e.pop(key, None))
            try:
                self._entry_path(key).unlink()
            except OSError:
                pass
        self._export_gauges()

    # --- telemetry ----------------------------------------------------------

    def _count(self, outcome: str, export: bool = False) -> None:
        with self._lock:
            self.counts[outcome] = self.counts.get(outcome, 0) + 1
        enabled, _tm = _tier_metrics()
        if not enabled:
            return
        if outcome in ("hit", "disk_hit"):
            _tm.CACHE_HITS.labels(tier=self.tier).inc()
        elif outcome == "miss":
            _tm.CACHE_MISSES.labels(tier=self.tier).inc()
        elif outcome == "evicted":
            _tm.CACHE_EVICTIONS.labels(tier=self.tier).inc()
        elif outcome == "corrupt":
            _tm.CACHE_CORRUPT.labels(tier=self.tier).inc()

    def _export_gauges(self) -> None:
        enabled, _tm = _tier_metrics()
        if not enabled:
            return
        with self._lock:
            _tm.CACHE_BYTES.labels(tier=self.tier).set(
                sum(e.nbytes for e in self._entries.values()))
            _tm.CACHE_ENTRIES.labels(tier=self.tier).set(
                len(self._entries))
