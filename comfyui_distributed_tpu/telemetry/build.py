"""The set-up ledger: every second between the process's start and its
first steady answer goes under ONE name.

Three families, all fed from code that runs only while a process boots, a
model is built or a program is built — never on a warm request:

- ``cdt_program_build_seconds{program, phase}``: what JAX spent on one
  program, by phase — ``trace``, ``lower``, ``cache_key`` + ``cache_read``
  (a hit of the persistent cache) or ``compile`` (anything else), and
  ``first_run`` (a labelled program's first call, net of the phases);
- ``cdt_weights_seconds{model, phase}``: ``init`` (a bundle's
  construction) and ``place`` (a tree's transfer onto a mesh) — and, counts
  beside them, ``cdt_weights_drawn_leaves_total{model}`` and
  ``cdt_weights_draw_programs_total{model}`` (``models/draw.py``);
- ``cdt_boot_seconds{phase}``: ``import``, ``backend``, ``controller``.

The ledger is EXCLUSIVE: building nests (an inner ``jit`` is traced inside
its caller's trace; a bundle's construction and a program's first call
hold whole builds), so every entry is SELF seconds — its wall time less
the build seconds that arrived on its thread while it ran. Those arrive
through :func:`note`, from the two ``jax.monitoring`` listeners below
(``utils/compile_cache.py`` registers them), which JAX calls on the thread
that traces, lowers and compiles, and are kept per thread as a running
total with the clock reading of each arrival: :func:`since` is the total's
growth after a reading, so a caller needs no mark taken BEFORE the work —
the reading it already took to time itself will do.

Stdlib-only, like the rest of the package.
"""

from __future__ import annotations

import bisect
import re
import threading
import time
from array import array
from contextlib import contextmanager

from . import metrics as _tm
from .registry import enabled
from .spans import STORE, record_span, set_span_attrs, span

BOOT_TRACE = "boot"

# Arrivals kept a thread (16 bytes each); older ones fold into [0], which
# is right for every reading later than they are. One trace of a model's
# initialiser holds ~20 000 inner traces, and ALL of them must still be
# there when it closes: a cap it could reach would count them twice.
_KEEP = 1 << 18


class _Thread(threading.local):
    """One thread's arrivals: clock readings, ascending, and the running
    total of build seconds after each; what JAX said of the cache inside
    the backend event that has not closed yet."""

    def __init__(self):
        self.at = array("d", [float("-inf")])
        self.total = array("d", [0.0])
        self.outcome = None         # hit | miss, from the cache's events
        self.retrieval = 0.0        # seconds of the read, on a hit
        self.model = ""             # the bundle whose weights.init is open
        self.pooled = False         # a thread of pooled_builds' pool


_mine = _Thread()


def note(seconds: float) -> None:
    """``seconds`` of build work ended now, on this thread."""
    at, total = _mine.at, _mine.total
    at.append(time.perf_counter())
    total.append(total[-1] + seconds)
    if len(at) > _KEEP:
        del at[:_KEEP // 2], total[:_KEEP // 2]
        at[0] = float("-inf")


def since(t0: float) -> float:
    """Build seconds that arrived on this thread at or after the
    ``time.perf_counter()`` reading ``t0``."""
    total = _mine.total
    return total[-1] - total[bisect.bisect_left(_mine.at, t0) - 1]


def self_seconds(t0: float, wall: float) -> float:
    """``wall`` seconds that began at ``t0``, less the build seconds that
    arrived meanwhile; never negative (two clocks, rounding)."""
    return max(0.0, wall - since(t0))


def _settle(t0: float, wall: float, record) -> None:
    """One entry of the ledger: the SELF seconds of ``wall`` from ``t0``
    go to ``record`` and, as build seconds, to whatever encloses them."""
    own = self_seconds(t0, wall)
    record(own)
    note(own)


_WRAPPED = re.compile(r"^(?:jit|pmap)(?:_(.+)|\((.+)\))$")


def program_of(fun_name: str) -> str:
    """One name a program: JAX says ``seg_body`` when it traces and
    ``jit(seg_body)`` (``jit_seg_body`` in a module's name) when it lowers
    and compiles."""
    wrapped = _WRAPPED.match(fun_name)
    if wrapped:
        return wrapped.group(1) or wrapped.group(2)
    return fun_name or "unnamed"


def _phase(program: str, phase: str):
    return _tm.PROGRAM_BUILD_SECONDS.labels(program=program,
                                            phase=phase).observe


def _phase_done(fun_name: str, phase: str, seconds: float) -> None:
    """A ``trace`` or ``lower`` event of ``seconds`` closed now: its SELF
    seconds go under the program's name (an inner program's build inside
    it has already been counted)."""
    _settle(time.perf_counter() - seconds, seconds,
            _phase(program_of(fun_name), phase))


def _backend_done(fun_name: str, seconds: float) -> None:
    """The backend event of ``seconds`` closed: what the cache's events
    said inside it (they carry no name) now has one. A hit splits into the
    read and the rest (the cache key, mostly); anything else compiled. On
    a pool's thread only the outcome counts: the seconds are the pool's."""
    program = program_of(fun_name)
    outcome, read = _mine.outcome, min(_mine.retrieval, seconds)
    _mine.outcome, _mine.retrieval = None, 0.0
    _tm.PROGRAM_CACHE.labels(program=program,
                             outcome=outcome or "uncached").inc()
    if _mine.pooled:
        return
    _tm.XLA_COMPILE_SECONDS.observe(seconds)
    if outcome == "hit":
        _phase(program, "cache_read")(read)
        _phase(program, "cache_key")(seconds - read)
    else:
        _phase(program, "compile")(seconds)
    note(seconds)


# --- the two jax.monitoring listeners (registered by utils/compile_cache.py) --
# JAX hands ``fun_name`` with the three duration events of a build. The
# cache's own events carry no name, but fire on the compiling thread INSIDE
# the backend event (jax/_src/compiler.py: compile_or_get_cached), so they
# are held per thread until that event closes with its name. The backend
# event is that function whole: on a hit it holds no compilation at all.

_OUTCOMES = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}


def on_event(event: str, **_) -> None:
    if event in _OUTCOMES:
        _mine.outcome = _OUTCOMES[event]
        _tm.COMPILE_CACHE_REQUESTS.labels(outcome=_mine.outcome).inc()
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _mine.outcome, _mine.retrieval = None, 0.0     # a new lookup


def on_duration(event: str, seconds: float, fun_name: str = "", **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _backend_done(fun_name, seconds)
    elif event in _PHASES and not _mine.pooled:
        _phase_done(fun_name, _PHASES[event], seconds)
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _mine.retrieval = seconds


def first_call(label: str, t0: float, dt: float) -> None:
    """A labelled program's first call took ``dt`` from ``t0``: the whole
    as before, and what is left of it once the build is taken out."""
    _tm.PIPELINE_COMPILE_SECONDS.labels(pipeline=label).observe(dt)
    _settle(t0, dt, _phase(label, "first_run"))


@contextmanager
def _ledger_span(name: str, record, **span_args):
    """A span whose SELF seconds go to ``record``; with telemetry off,
    nothing. Yields ``set_span_attrs``: attributes known only at the end."""
    if not enabled():
        yield set_span_attrs
        return
    t0 = time.perf_counter()
    try:
        with span(name, **span_args):
            yield set_span_attrs
    finally:
        _settle(t0, time.perf_counter() - t0, record)


@contextmanager
def weights_span(phase: str, model: str, **attrs):
    """A ``weights.<phase>`` span whose SELF seconds land in
    ``cdt_weights_seconds{model, phase}``; what is drawn on this thread
    while it is open counts under ``model`` (:func:`weights_drawn`)."""
    def record(seconds: float) -> None:
        _tm.WEIGHTS_SECONDS.labels(model=model, phase=phase).observe(seconds)

    outer, _mine.model = _mine.model, model
    try:
        with _ledger_span(f"weights.{phase}", record, model=model,
                          **attrs) as set_attrs:
            yield set_attrs
    finally:
        _mine.model = outer


def in_pool() -> None:
    """This thread builds for :func:`pooled_builds` (a pool's
    ``initializer``): its builds' seconds are not its own."""
    _mine.pooled = True


@contextmanager
def pooled_builds(program: str):
    """Programs built SIDE BY SIDE on :func:`in_pool` threads while this is
    open are one entry of the ledger: the wall seconds the opening thread
    waited, under ``program``'s ``compile`` (their traces, lowerings and
    cache reads too: seconds summed over threads would be counted twice)."""
    if not enabled():
        yield
        return

    def record(seconds: float) -> None:
        _tm.XLA_COMPILE_SECONDS.observe(seconds)    # as a backend event's
        _phase(program, "compile")(seconds)

    t0 = time.perf_counter()
    try:
        yield
    finally:
        _settle(t0, time.perf_counter() - t0, record)


def weights_drawn(leaves: int, programs: int) -> None:
    """``models/draw.py`` drew ``leaves`` random-weight leaves through
    ``programs`` distinct programs, for the bundle being built here."""
    _tm.WEIGHTS_DRAWN_LEAVES.labels(model=_mine.model).inc(leaves)
    _tm.WEIGHTS_DRAW_PROGRAMS.labels(model=_mine.model).inc(programs)


def _boot_seconds(phase: str):
    def record(seconds: float) -> None:
        STORE.pin(BOOT_TRACE)   # read long after the ring of traces turned
        _tm.BOOT_SECONDS.labels(phase=phase).inc(seconds)

    return record


def boot_phase(phase: str):
    """A ``boot.<phase>`` span under the one trace id ``boot``; its SELF
    seconds are added to ``cdt_boot_seconds{phase}`` (a phase may come in
    two pieces: the imports either side of the backend's start)."""
    return _ledger_span(f"boot.{phase}", _boot_seconds(phase),
                        trace_id=BOOT_TRACE)


def boot_elapsed(phase: str, t0: float) -> None:
    """A boot phase that began at ``t0`` — before this module could be
    imported — ends now."""
    if enabled():
        wall = time.perf_counter() - t0
        record_span(f"boot.{phase}", wall, trace_id=BOOT_TRACE)
        _settle(t0, wall, _boot_seconds(phase))
