"""Span tracing: nested wall-clock spans with cross-HTTP trace stitching.

``span("denoise_step", job_id=...)`` opens a timed span; spans nest via a
``contextvars.ContextVar`` so asyncio handlers and plain call stacks both
get correct parent linkage without threading anything through signatures.
Every finished span is recorded into the process-global ``STORE`` (bounded
ring of traces) and its duration lands in the ``cdt_span_seconds{name=…}``
histogram.

Cross-host stitching: an active span context serializes into the
``X-CDT-Trace`` header (``trace_id:span_id``) via ``trace_headers()``; the
receiving side parses it (``parse_trace_header``) and enters the same
trace with ``use_trace(trace_id, parent_span_id)`` — so a master's
dispatch span and the worker's execution span share one trace ID and a
real parent/child edge, and ``/distributed/trace/{job_id}`` can assemble
both sides into one timeline.

The orchestration layer's existing ``exec_…`` trace IDs are adopted
verbatim (``span(..., trace_id=…)``), so log lines and span trees
correlate on the same key.

One clock with the profiler: while a profile session runs, the profile
routes install an *annotator* (``set_annotator`` — a factory of context
managers, ``jax.profiler.TraceAnnotation`` in practice; this module stays
stdlib-only) and every span opened in synchronous code is mirrored as an
annotation named ``cdt.<span name>``, so it lands in the host plane of the
same ``.xplane.pb`` as the device's operations, on their timebase. An
annotation belongs to a thread, so spans opened inside a running asyncio
task (they interleave on the loop's thread) are not mirrored. With no
session the cost is one ``is None`` test per span.
"""

from __future__ import annotations

import asyncio
import contextvars
import secrets
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Optional

from .registry import REGISTRY, enabled

TRACE_HEADER = "X-CDT-Trace"

# (trace_id, span_id, attrs) of the innermost active span; span_id may be ""
# and attrs None when only a remote parent context was adopted (use_trace
# without a local span)
_CTX: "contextvars.ContextVar[Optional[tuple[str, str, Optional[dict]]]]" = \
    contextvars.ContextVar("cdt_trace", default=None)

_SPAN_SECONDS = REGISTRY.histogram(
    "cdt_span_seconds",
    "Wall-clock duration of telemetry spans, by span name.",
    ("name",))

# span attributes that double as lookup keys for /distributed/trace/{id}
_INDEX_ATTRS = ("job_id", "prompt_id")


def new_trace_id() -> str:
    return f"trace_{int(time.time() * 1000)}_{secrets.token_hex(3)}"


class SpanStore:
    """Bounded in-memory ring of finished spans, grouped by trace.

    Oldest traces are evicted first, a pinned one (``pin``: the process's
    ``boot`` trace) never; a single trace is capped so a runaway loop
    cannot grow one entry without bound. ``resolve`` maps a job or prompt
    id (seen as a span attribute) back to its trace."""

    def __init__(self, max_traces: int = 256, max_spans: int = 512):
        self.max_traces = max_traces
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, list[dict]]" = OrderedDict()
        self._by_key: dict[str, str] = {}
        self._pinned: set[str] = set()

    def pin(self, trace_id: str) -> None:
        """Keep that trace out of the eviction order."""
        self._pinned.add(trace_id)

    def record(self, span: dict) -> None:
        tid = span["trace_id"]
        with self._lock:
            spans = self._traces.get(tid)
            if spans is None:
                spans = self._traces[tid] = []
                while len(self._traces) > self.max_traces:
                    old_tid = next(t for t in self._traces
                                   if t not in self._pinned)
                    del self._traces[old_tid]
                    for k in [k for k, v in self._by_key.items()
                              if v == old_tid]:
                        del self._by_key[k]
            if len(spans) < self.max_spans:
                spans.append(span)
            for attr in _INDEX_ATTRS:
                v = span.get("attrs", {}).get(attr)
                if v:
                    self._by_key[str(v)] = tid

    def resolve(self, key: str) -> Optional[str]:
        """Trace id for a trace id, job id, or prompt id."""
        with self._lock:
            if key in self._traces:
                return key
            return self._by_key.get(key)

    def spans(self, trace_id: str) -> list[dict]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def tree(self, trace_id: str) -> list[dict]:
        """Nested span forest (roots may be plural: master and worker both
        contribute top-level spans to one trace)."""
        spans = sorted(self.spans(trace_id), key=lambda s: s["start"])
        nodes = {s["span_id"]: {**s, "children": []} for s in spans}
        roots: list[dict] = []
        for s in spans:
            parent = nodes.get(s.get("parent_id") or "")
            target = parent["children"] if parent is not None else roots
            target.append(nodes[s["span_id"]])
        return roots

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()
            self._by_key.clear()


STORE = SpanStore()

ANNOTATION_PREFIX = "cdt."

# name -> context manager, or None (no profile session: nothing mirrored)
_ANNOTATOR = None


def set_annotator(factory) -> None:
    """Install (or, with None, remove) the factory whose context managers
    mirror synchronous spans into the profiler's trace."""
    global _ANNOTATOR
    _ANNOTATOR = factory


def _in_asyncio_task() -> bool:
    try:
        asyncio.get_running_loop()
    except RuntimeError:
        return False
    return True


@contextmanager
def span(name: str, trace_id: Optional[str] = None,
         parent_id: Optional[str] = None, **attrs):
    """Timed span context manager. No-op (yields ``None``) when telemetry
    is disabled — the guard is the first thing that runs, so the disabled
    hot path costs one boolean read.

    ``trace_id`` adopts an existing trace (e.g. the orchestrator's
    ``exec_…`` id); omitted, the span joins the ambient trace or starts a
    fresh one. ``parent_id`` overrides parent linkage for cross-process
    stitching (the worker's execution span parents onto the master's
    dispatch span id carried by ``X-CDT-Trace``)."""
    if not enabled():
        yield None
        return
    cur = _CTX.get()
    if trace_id is None:
        trace_id = cur[0] if cur else new_trace_id()
    if parent_id is None and cur and cur[0] == trace_id:
        parent_id = cur[1] or None
    span_id = secrets.token_hex(4)
    token = _CTX.set((trace_id, span_id, attrs))
    annotation = None
    if _ANNOTATOR is not None and not _in_asyncio_task():
        annotation = _ANNOTATOR(ANNOTATION_PREFIX + name)
        annotation.__enter__()
    start = time.time()
    t0 = time.perf_counter()
    error = None
    try:
        yield (trace_id, span_id)
    except BaseException as e:
        error = f"{type(e).__name__}: {e}"
        raise
    finally:
        duration = time.perf_counter() - t0
        if annotation is not None:
            annotation.__exit__(None, None, None)
        _CTX.reset(token)
        _finish(name, trace_id, span_id, parent_id, start, duration, attrs,
                error)


def _finish(name, trace_id, span_id, parent_id, start, duration, attrs,
            error=None) -> None:
    rec = {
        "name": name,
        "trace_id": trace_id,
        "span_id": span_id,
        "parent_id": parent_id,
        "start": start,
        "duration_s": duration,
        "attrs": {k: str(v) for k, v in attrs.items()},
    }
    if error is not None:
        rec["error"] = error
    STORE.record(rec)
    _SPAN_SECONDS.labels(name=name).observe(duration)


def set_span_attrs(**attrs) -> None:
    """Add attributes to the innermost active span: what is known only
    once its work is done (the bytes of what it built)."""
    cur = _CTX.get()
    if cur and cur[2] is not None:
        cur[2].update(attrs)


@contextmanager
def timed_span(name: str, histogram, **attrs):
    """A span whose duration is also observed into ``histogram`` (a
    metric child with ``observe``): the callers that feed a family of
    their own keep their clock reads in here."""
    if not enabled():
        yield None
        return
    t0 = time.perf_counter()
    try:
        with span(name, **attrs) as ctx:
            yield ctx
    finally:
        histogram.observe(time.perf_counter() - t0)


def record_span(name: str, duration_s: float,
                trace_id: Optional[str] = None,
                parent_id: Optional[str] = None, **attrs) -> None:
    """Record a span that was not lived through as a ``with`` block: it
    ended now and lasted ``duration_s`` (time in a queue, say, known only
    once the wait is over). Same store, same histogram; never mirrored,
    since an annotation cannot be opened in the past."""
    if not enabled():
        return
    cur = _CTX.get()
    if trace_id is None:
        trace_id = cur[0] if cur else new_trace_id()
    if parent_id is None and cur and cur[0] == trace_id:
        parent_id = cur[1] or None
    _finish(name, trace_id, secrets.token_hex(4), parent_id,
            time.time() - duration_s, duration_s, attrs)


@contextmanager
def use_trace(trace_id: str, parent_span_id: Optional[str] = None):
    """Adopt a remote trace context (parsed from ``X-CDT-Trace``) for the
    duration of the block: spans opened inside join ``trace_id`` with
    ``parent_span_id`` as their parent."""
    token = _CTX.set((trace_id, parent_span_id or "", None))
    try:
        yield
    finally:
        _CTX.reset(token)


def current_trace_id() -> Optional[str]:
    cur = _CTX.get()
    return cur[0] if cur else None


def current_span_id() -> Optional[str]:
    cur = _CTX.get()
    return (cur[1] or None) if cur else None


def trace_headers() -> dict:
    """``{"X-CDT-Trace": "trace_id:span_id"}`` for the active context, or
    ``{}`` — safe to splat into any outbound request's headers."""
    if not enabled():
        return {}
    cur = _CTX.get()
    if not cur:
        return {}
    tid, sid = cur[:2]
    return {TRACE_HEADER: f"{tid}:{sid}" if sid else tid}


def parse_trace_header(value) -> Optional[tuple[str, Optional[str]]]:
    """``"trace_id[:span_id]"`` → ``(trace_id, span_id | None)``; None on
    anything malformed (headers are peer-controlled input)."""
    if not isinstance(value, str) or not value or len(value) > 200:
        return None
    tid, _, sid = value.partition(":")
    tid = tid.strip()
    if not tid:
        return None
    return tid, (sid.strip() or None)
