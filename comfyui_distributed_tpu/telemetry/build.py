"""The set-up ledger: every second between the process's start and its
first steady answer goes under ONE name.

Five families, all fed from code that runs only while a process boots, a
model is built or a program is built — never on a warm request:

- ``cdt_program_build_seconds{program, phase}``: what JAX spent on one
  program, by phase — ``trace``, ``lower``, ``cache_key`` + ``cache_read``
  (a hit of the persistent cache) or ``compile`` (anything else), and
  ``first_run`` (a labelled program's first call, net of the phases);
- ``cdt_weights_seconds{model, phase}``: ``init`` (a bundle's
  construction) and ``place`` (a tree's transfer onto a mesh) — and, counts
  beside them, ``cdt_weights_drawn_leaves_total{model}`` and
  ``cdt_weights_draw_programs_total{model}`` (``models/draw.py``);
- ``cdt_boot_seconds{phase}``: ``import``, ``backend``, ``controller``;
- ``cdt_program_build_under_seconds{under, phase}``: the first family's
  seconds again, by the entry that ENCLOSED the build (:func:`_claim`);
- ``cdt_program_cold_compile_seconds{program}``: the compile each program
  stands for, whether this process compiled it or read it.

And a timeline beside the sums: every trace, lowering, cache read or
compile of ``SPAN_FLOOR`` seconds and more is a ``build.<phase>`` span of
the trace it ran in (:func:`on_time_span`).

The ledger is EXCLUSIVE: building nests (an inner ``jit`` is traced inside
its caller's trace; a bundle's construction and a program's first call
hold whole builds), so every entry is SELF seconds — its wall time less
the build seconds that arrived on its thread while it ran. Those arrive
through :func:`note`, from the three ``jax.monitoring`` listeners below
(``utils/compile_cache.py`` registers them), which JAX calls on the thread
that traces, lowers and compiles, and are kept per thread as a running
total with the clock reading of each arrival: :func:`since` is the total's
growth after a reading, so a caller needs no mark taken BEFORE the work —
the reading it already took to time itself will do.

Stdlib-only, like the rest of the package.
"""

from __future__ import annotations

import bisect
import os
import re
import sys
import sysconfig
import threading
import time
from array import array
from contextlib import contextmanager

from . import metrics as _tm
from .registry import enabled
from .spans import (STORE, current_span_id, current_trace_id, record_span,
                    set_span_attrs, span)

BOOT_TRACE = "boot"
NOBODY = "-"            # ``under`` of a build that no entry enclosed
PHASES = ("trace", "lower", "cache_key", "cache_read", "compile", "first_run")
SPAN_FLOOR = 0.05       # seconds: a shorter build is a sum and no span
MAX_SITES = 32          # call sites of anonymous programs named a process

# Arrivals kept a thread (16 bytes each, and 17 more while unclaimed); older
# ones fold into [0], which is right for every reading later than they are.
# One trace of a model's forward holds ~20 000 inner traces, and ALL of
# them must still be there when it closes: a cap it could reach would count
# them twice.
_KEEP = 1 << 18


class _Thread(threading.local):
    """One thread's arrivals: clock readings, ascending, and the running
    total of build seconds after each; those of them no entry has claimed
    yet; what JAX said of the cache inside the backend event that has not
    closed yet, and of the build whose span event comes next."""

    def __init__(self):
        self.at = array("d", [float("-inf")])
        self.total = array("d", [0.0])
        self.loose_at = array("d")      # the unclaimed ones: when,
        self.loose_phase = array("b")   # ... which of PHASES,
        self.loose_seconds = array("d")     # ... and how long;
        self.folded = None          # ... by phase, those the cap let go
        self.outcome = None         # hit | miss, from the cache's events
        self.retrieval = 0.0        # seconds of the read, on a hit
        self.saved = 0.0            # compile seconds the hit's entry saved
        self.built = None           # the build whose span event is next
        self.model = ""             # the bundle whose weights.init is open
        self.held = 0               # weights.* / boot.* / pool entries open
        self.pooled = False         # a thread of pooled_builds' pool
        self.opener = (None, None)  # ... and its opener's trace and span


_mine = _Thread()


# What is under nobody stays where it arrived — ~20 000 builds a set-up,
# most of them claimed a moment later — and is summed into its series only
# when somebody reads the family: here, every thread that ever held a
# program's build, by (loose_phase, loose_seconds, folded).
_nobodys: list = []
_nobodys_lock = threading.Lock()


def _publish_nobodys() -> None:
    """``cdt_program_build_under_seconds{under="-"}`` as of now."""
    if not enabled():
        return
    sums = [0.0] * len(PHASES)
    with _nobodys_lock:
        threads = list(_nobodys)
    for phases, seconds, folded in threads:
        for which, gone in enumerate(folded):
            sums[which] += gone
        for which, one in zip(phases, seconds):
            sums[which] += one
    for phase, seconds in zip(PHASES, sums):
        _tm.PROGRAM_BUILD_UNDER_SECONDS.labels(under=NOBODY,
                                               phase=phase).set(seconds)


_tm.PROGRAM_BUILD_UNDER_SECONDS.before_read = _publish_nobodys


def reset() -> None:
    """Forget what waits unclaimed and which call sites have a name: test
    isolation, beside ``REGISTRY.reset()``. Only the calling thread's own
    history is emptied; other threads' is let go of."""
    with _nobodys_lock:
        del _nobodys[:]
    with _sites_lock:
        _sites.clear()
    for column in (_mine.loose_at, _mine.loose_phase, _mine.loose_seconds):
        del column[:]
    _mine.folded = None


def note(seconds: float, phase: str = "") -> None:
    """``seconds`` of build work ended now, on this thread. With a
    ``phase`` they are a program's (``cdt_program_build_seconds`` holds
    them) and wait, under nobody, for the entry that closes around them."""
    at, total = _mine.at, _mine.total
    now = time.perf_counter()
    at.append(now)
    total.append(total[-1] + seconds)
    if len(at) > _KEEP:
        del at[:_KEEP // 2], total[:_KEEP // 2]
        at[0] = float("-inf")
    if phase:
        loose, phases, lasted = (_mine.loose_at, _mine.loose_phase,
                                 _mine.loose_seconds)
        if _mine.folded is None:        # this thread's first: the reader's
            _mine.folded = [0.0] * len(PHASES)
            with _nobodys_lock:
                _nobodys.append((phases, lasted, _mine.folded))
        loose.append(now)
        phases.append(PHASES.index(phase))
        lasted.append(seconds)
        if len(loose) > _KEEP:      # what nothing claimed stays nobody's
            for which, gone in zip(phases[:_KEEP // 2], lasted[:_KEEP // 2]):
                _mine.folded[which] += gone
            del loose[:_KEEP // 2], phases[:_KEEP // 2], lasted[:_KEEP // 2]


def _claim(t0: float, under: str) -> None:
    """The entry ``under`` that began at ``t0`` closes now: the builds
    that arrived on this thread since and that no inner entry took (inner
    entries close first, so innermost wins) move from nobody to it."""
    at, phases, seconds = (_mine.loose_at, _mine.loose_phase,
                           _mine.loose_seconds)
    first = bisect.bisect_left(at, t0)
    mine = [0.0] * len(PHASES)
    for i in range(first, len(at)):
        mine[phases[i]] += seconds[i]
    del at[first:], phases[first:], seconds[first:]
    for phase, moved in zip(PHASES, mine):
        if moved:
            _tm.PROGRAM_BUILD_UNDER_SECONDS.labels(under=under,
                                                   phase=phase).inc(moved)


def since(t0: float) -> float:
    """Build seconds that arrived on this thread at or after the
    ``time.perf_counter()`` reading ``t0``."""
    total = _mine.total
    return total[-1] - total[bisect.bisect_left(_mine.at, t0) - 1]


def self_seconds(t0: float, wall: float) -> float:
    """``wall`` seconds that began at ``t0``, less the build seconds that
    arrived meanwhile; never negative (two clocks, rounding)."""
    return max(0.0, wall - since(t0))


def _settle(t0: float, wall: float, record, under: str,
            phase: str = "") -> float:
    """One entry of the ledger, ``under``: the SELF seconds of ``wall``
    from ``t0`` go to ``record`` and, as build seconds (of ``phase``, where
    they are a program's), to whatever encloses them; the builds inside
    are its own to answer for."""
    own = self_seconds(t0, wall)
    record(own)
    loose = _mine.loose_at
    if loose and loose[-1] >= t0:
        _claim(t0, under)
    note(own, phase)
    return own


_WRAPPED = re.compile(r"^(?:jit|pmap)(?:_(.+)|\((.+)\))$")


def program_of(fun_name: str) -> str:
    """One name a program: JAX says ``seg_body`` when it traces and
    ``jit(seg_body)`` (``jit_seg_body`` in a module's name) when it lowers
    and compiles."""
    wrapped = _WRAPPED.match(fun_name)
    if wrapped:
        return wrapped.group(1) or wrapped.group(2)
    return fun_name or "unnamed"


# --- an anonymous program is named by the line that called it ----------------
# JAX hands the listeners a ``fun_name`` and nothing else, and a jitted
# lambda's says nothing; but they run on the thread that builds, INSIDE the
# call that asked for the build, so the stack says who asked.

_HERE = os.path.abspath(__file__)
_PACKAGE = os.path.dirname(os.path.dirname(_HERE)) + os.sep
_INSTALLED = "site-packages" + os.sep
_STDLIB = sysconfig.get_paths()["stdlib"] + os.sep   # contextlib, functools
_JAX, _FLAX = ("jax" + os.sep, "jaxlib" + os.sep), "flax" + os.sep
_ANONYMOUS = ("<lambda>", "unnamed")
_sites: set = set()
_sites_lock = threading.Lock()


def _call_site() -> tuple:
    """``file:line`` of the first frame outwards from here that is neither
    this module's, JAX's nor the standard library's, the file as its package
    spells it; where that frame is flax's, ``<`` and this package's first
    frame below it: which line of which model made flax ask. And flax's
    part alone (else empty): the name left when the sites run out."""
    frame, site = sys._getframe(1), ""
    while frame is not None:
        filename, line = frame.f_code.co_filename, frame.f_lineno
        frame = frame.f_back
        if filename.startswith(_PACKAGE):
            if filename == _HERE:
                continue
            ours = f"{filename[len(_PACKAGE):]}:{line}"
            return (f"{site}<{ours}", site) if site else (ours, "")
        if site or filename.startswith("<"):
            continue
        _, installed, inside = filename.rpartition(_INSTALLED)
        if not installed:
            if filename.startswith(_STDLIB):
                continue
            inside = os.sep.join(filename.split(os.sep)[-2:])
        elif inside.startswith(_JAX):
            continue
        site = f"{inside}:{line}"
        if not inside.startswith(_FLAX):
            return site, ""
    return site or "nowhere", site


def _named(fun_name: str) -> str:
    """:func:`program_of`, and for a program with no name of its own
    ``<lambda>@<call site>``: the first ``MAX_SITES`` sites a process; past
    them flax's line alone (flax has few) and else ``@other`` — a site made
    by a loop over traffic stays bounded."""
    program = program_of(fun_name)
    if program not in _ANONYMOUS:
        return program
    site, flax_line = _call_site()
    with _sites_lock:
        if site not in _sites:
            if len(_sites) >= MAX_SITES:
                site = flax_line or "other"
            else:
                _sites.add(site)
    return f"{program}@{site}"


def _phase(program: str, phase: str):
    return _tm.PROGRAM_BUILD_SECONDS.labels(program=program,
                                            phase=phase).observe


def _phase_done(fun_name: str, phase: str, seconds: float) -> None:
    """A ``trace`` or ``lower`` event of ``seconds`` closed now: its SELF
    seconds go under the program's name (an inner program's build inside
    it has already been counted). A pool's thread keeps the name alone."""
    program = _named(fun_name)
    own = seconds if _mine.pooled else _settle(
        time.perf_counter() - seconds, seconds, _phase(program, phase),
        program, phase)
    if seconds >= SPAN_FLOOR:
        _mine.built = (phase, seconds, program, own, "")


def _backend_done(fun_name: str, seconds: float) -> None:
    """The backend event of ``seconds`` closed: what the cache's events
    said inside it (they carry no name) now has one. A hit splits into the
    read and the rest (the cache key, mostly) and stands for the compile
    its entry holds; anything else compiled. On a pool's thread the
    ledger's seconds are the pool's: only the outcome counts, and the
    compile the program stands for."""
    program = _named(fun_name)
    outcome, retrieval = _mine.outcome or "uncached", _mine.retrieval
    hit, saved = outcome == "hit", _mine.saved
    _mine.outcome, _mine.retrieval, _mine.saved = None, 0.0, 0.0
    _tm.PROGRAM_CACHE.labels(program=program, outcome=outcome).inc()
    _tm.PROGRAM_COLD_COMPILE_SECONDS.labels(program=program).inc(
        max(0.0, saved + retrieval) if hit else seconds)
    if seconds >= SPAN_FLOOR:
        _mine.built = ("cache_read" if hit else "compile", seconds, program,
                       seconds, outcome)
    if _mine.pooled:
        return
    _tm.XLA_COMPILE_SECONDS.observe(seconds)
    if hit:
        read = min(retrieval, seconds)
        _phase(program, "cache_read")(read)
        _phase(program, "cache_key")(seconds - read)
        note(seconds - read, "cache_key")
        note(read, "cache_read")
    else:
        _phase(program, "compile")(seconds)
        note(seconds, "compile")


def _build_span(phase: str, seconds: float, program: str, own: float,
                outcome: str) -> None:
    """A ``build.<phase>`` span of ``seconds`` (``own`` of them its SELF
    seconds; ``outcome`` where the cache had one) that ended now, in the
    trace this thread works for — a pool's thread for its opener's, under
    the span that opened the pool. Outside every ``weights.*``, ``boot.*``
    and pool entry a request is paying for a program (``warm=false``): its
    first call in a warm-up, a recompile on a live request. A thread with no
    trace (a script, a test) gets no span: a trace a build would turn the
    ring."""
    trace, parent = _mine.opener if _mine.pooled else (current_trace_id(),
                                                       current_span_id())
    if trace is None:
        return
    attrs = {"outcome": outcome} if outcome else {}
    if not _mine.held and trace != BOOT_TRACE:
        attrs["warm"] = "false"
    record_span(f"build.{phase}", seconds, trace_id=trace, parent_id=parent,
                program=program, thread=threading.current_thread().name,
                self_s=f"{own:.6f}", **attrs)


# --- the three jax.monitoring listeners (registered by utils/compile_cache.py)
# JAX hands ``fun_name`` with the three duration events of a build, and each
# again, at once and on the same thread, as a span event with its start and
# end. The cache's own events carry no name, but fire on the compiling
# thread INSIDE the backend event (jax/_src/compiler.py:
# compile_or_get_cached), so they are held per thread until that event
# closes with its name. The backend event is that function whole: on a hit it
# holds no compilation at all.

_OUTCOMES = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
_BACKEND = "/jax/core/compile/backend_compile_duration"


def on_event(event: str, **_) -> None:
    if not enabled():
        return
    if event in _OUTCOMES:
        _mine.outcome = _OUTCOMES[event]
        _tm.COMPILE_CACHE_REQUESTS.labels(outcome=_mine.outcome).inc()
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _mine.outcome, _mine.retrieval, _mine.saved = None, 0.0, 0.0


def on_duration(event: str, seconds: float, fun_name: str = "", **_) -> None:
    if not enabled():
        return
    if event == _BACKEND:
        _backend_done(fun_name, seconds)
    elif event in _PHASES:
        _phase_done(fun_name, _PHASES[event], seconds)
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _mine.retrieval = seconds
    elif event == "/jax/compilation_cache/compile_time_saved_sec":
        _mine.saved = seconds


def on_time_span(event: str, start: float, end: float, **_) -> None:
    """The span event of the build whose duration event just closed on this
    thread (JAX sends the two back to back): where that one was of
    ``SPAN_FLOOR`` and more it left what it worked out, and the build is a
    span on the set-up's timeline."""
    built = _mine.built
    if built is not None:
        _mine.built = None
        _build_span(*built)


def first_call(label: str, t0: float, dt: float) -> None:
    """A labelled program's first call took ``dt`` from ``t0``: the whole
    as before, and what is left of it once the build is taken out."""
    _tm.PIPELINE_COMPILE_SECONDS.labels(pipeline=label).observe(dt)
    _settle(t0, dt, _phase(label, "first_run"), f"first_run:{label}",
            "first_run")


@contextmanager
def _ledger_span(name: str, record, under: str = "", **span_args):
    """A span whose SELF seconds go to ``record`` and whose builds go
    under ``under`` (its name, unless said); with telemetry off, nothing.
    Yields ``set_span_attrs``: attributes known only at the end."""
    if not enabled():
        yield set_span_attrs
        return
    t0 = time.perf_counter()
    _mine.held += 1
    try:
        with span(name, **span_args):
            yield set_span_attrs
    finally:
        _mine.held -= 1
        _settle(t0, time.perf_counter() - t0, record, under or name)


@contextmanager
def weights_span(phase: str, model: str, **attrs):
    """A ``weights.<phase>`` span whose SELF seconds land in
    ``cdt_weights_seconds{model, phase}``; what is drawn on this thread
    while it is open counts under ``model`` (:func:`weights_drawn`)."""
    def record(seconds: float) -> None:
        _tm.WEIGHTS_SECONDS.labels(model=model, phase=phase).observe(seconds)

    outer, _mine.model = _mine.model, model
    try:
        with _ledger_span(f"weights.{phase}", record,
                          under=f"weights.{phase}:{model}", model=model,
                          **attrs) as set_attrs:
            yield set_attrs
    finally:
        _mine.model = outer


def in_pool(opener=(None, None)) -> None:
    """This thread builds for :func:`pooled_builds` (a pool's
    ``initializer``): its builds' seconds are not its own, and their spans
    go where ``opener`` — what ``pooled_builds`` yielded — says (a pool's
    thread inherits no context of its own)."""
    _mine.pooled, _mine.held, _mine.opener = True, 1, opener


@contextmanager
def pooled_builds(program: str):
    """Programs built SIDE BY SIDE on :func:`in_pool` threads while this is
    open are one entry of the ledger: the wall seconds the opening thread
    waited, under ``program``'s ``compile`` (their traces, lowerings and
    cache reads too: seconds summed over threads would be counted twice).
    Yields the opener's trace and span, for ``in_pool``."""
    if not enabled():
        yield None, None
        return

    def record(seconds: float) -> None:
        _tm.XLA_COMPILE_SECONDS.observe(seconds)    # as a backend event's
        _phase(program, "compile")(seconds)

    t0 = time.perf_counter()
    _mine.held += 1
    try:
        yield current_trace_id(), current_span_id()
    finally:
        _mine.held -= 1
        wall = time.perf_counter() - t0
        own = _settle(t0, wall, record, program, "compile")
        if wall >= SPAN_FLOOR:
            _build_span("compile", wall, program, own, "pooled")


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
        _settle(t0, wall, _boot_seconds(phase), f"boot.{phase}")
