"""In-flight sampling progress: per-step x0 streaming out of compiled code.

The reference inherits per-step progress bars and live latent previews
from ComfyUI's executor hooks (its UI polls them; SURVEY "external
substrate"). In a jit-compiled world the sampler scan is one XLA program,
so progress must stream out *through* the compiled boundary:
``wrap_denoiser`` interposes on the (guided) denoiser and emits
``jax.debug.callback`` effects carrying ``(token, shard, sigma, x0)``.
The payload is one latent (`x0[:1]`, ~256 KB for SDXL), but an event is
NOT free: on the TPU a callback is a host send plus a receive the program
waits on, so every event idles the chip for one host round trip (2.9 ms
measured under ``serve``) and hands it any stall of the host — a frozen
host freezes the chip at its next event, a chip with no event due runs
through (PERF.md §6, PR 25). So the stream is rate-limited on the device:
``token`` may carry a *stride*, and inside a sampler scan (which tells
the wrapper its step through ``sampler_step``) only every stride-th step
reports, each event standing for ``stride`` calls.

``token`` is *traced* — an int32 scalar, or ``[token, stride]`` — so one
compiled program serves every job and every stride: the host allocates a
fresh token per run and the callback routes on its runtime value.
Callbacks are unordered; ``sigma`` (strictly decreasing over the ladder)
is the ordering key the sink uses to keep the newest preview and a
monotonic step count.

Which lanes stream how (PR 27). A program with a host callback is never
written to JAX's persistent compile cache and its Python launch costs
~0.055 s, so the two SERVED lanes carry none: ``TPUTxt2Img``'s serving
lane (``Txt2ImgPipeline.generate_preemptible``) and ``TPUFlowTxt2Img``'s
``dp`` mode (``FlowPipeline.generate_segmented``) run callback-free
segment programs whose denoiser is wrapped in a :class:`DenoiserTap`: the
x0 of a segment's last step leaves the program as an ordinary output and
the host hands it to the same sinks when the segment is over
(:func:`deliver_segment`), one event a segment and chip standing for the
segment's calls. Every other compiled program — the monolithic
``generate_fn`` builders (ControlNet graphs, ``CDT_PREEMPT=0``), img2img,
near, video, microbatch, the resident offload ladders — still streams
through ``wrap_denoiser``'s callback, stride and all.
``cdt_progress_events_total{source}`` says which path fed the stream.

This module is deliberately free of cluster/HTTP imports: sinks are
registered (``add_sink``) by ``cluster/progress.ProgressTracker``.

Multiple sinks may be registered at once (an embedded master+worker pair,
or two Controllers in one test process, each own a tracker): every event
is fanned out to every sink, and routing falls out of token uniqueness —
``next_token`` is a process-global allocator, so a tracker's job table
simply misses on tokens it didn't issue.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
from collections import OrderedDict
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import enabled as _tm_enabled
from ..telemetry import metrics as _tm
from ..telemetry import spans as _spans
from ..telemetry.device_scopes import device_scope

# sink(token:int, shard:int, sigma:float, x0:np.ndarray, calls:int), calls
# being the denoiser calls the event stands for. Registry keyed by handle so
# removal is exact; empty = events dropped on the floor.
_LOCK = threading.Lock()
_SINKS: "dict[int, Callable]" = {}
_HANDLES = itertools.count(1)
_TOKENS = itertools.count(1)
# token -> (trace id, span id) of the code that asked for the token: the
# callbacks run on runtime threads, which inherit no span context, and
# their ``progress.sink`` spans belong in the request's tree
_ORIGIN: "OrderedDict[int, tuple]" = OrderedDict()
_ORIGIN_KEEP = 64


def next_token() -> int:
    """Process-globally unique progress token. One compiled program, one
    callback route: uniqueness across *all* trackers is what lets every
    sink receive every event and key only on its own jobs."""
    with _LOCK:
        token = next(_TOKENS)
        trace_id = _spans.current_trace_id()
        if trace_id is not None:
            _ORIGIN[token] = (trace_id, _spans.current_span_id())
            while len(_ORIGIN) > _ORIGIN_KEEP:
                _ORIGIN.popitem(last=False)
        return token


def add_sink(fn: Callable) -> int:
    """Register an event sink; returns a handle for ``remove_sink``."""
    with _LOCK:
        handle = next(_HANDLES)
        _SINKS[handle] = fn
        return handle


def remove_sink(handle: int) -> None:
    with _LOCK:
        _SINKS.pop(handle, None)


def set_sink(fn: Optional[Callable]) -> None:
    """Legacy single-sink setter: clears the registry, then installs
    ``fn`` (if not None) as the only sink. Kept for tests/embedders that
    want exclusive capture."""
    with _LOCK:
        _SINKS.clear()
        if fn is not None:
            _SINKS[next(_HANDLES)] = fn


def get_sink() -> Optional[Callable]:
    """Any currently-registered sink (newest), or None. Legacy accessor."""
    with _LOCK:
        if not _SINKS:
            return None
        return _SINKS[max(_SINKS)]


def _dispatch(token, shard, sigma, x0) -> None:
    token, calls = np.asarray(token), 1
    if token.ndim:                 # [token, calls]: a strided event
        token, calls = token[0], int(token[1])
    with _LOCK:
        sinks = list(_SINKS.values())
        trace_id, parent_id = _ORIGIN.get(int(token), (None, None))
    if _tm_enabled():
        _tm.PROGRESS_EVENTS.labels(source="callback").inc()
    with _spans.timed_span("progress.sink", _tm.PROGRESS_CALLBACK_SECONDS,
                           trace_id=trace_id, parent_id=parent_id):
        for sink in sinks:
            try:
                sink(int(token), int(shard), float(sigma), np.asarray(x0),
                     calls)
            except Exception:  # a broken UI consumer must never kill a job
                pass


# model calls the wrapped (guided) denoiser makes per sampler step; CFG is
# batch-concatenated into one call (guidance.cfg_denoiser) so it doesn't
# multiply. Second-order samplers call twice per step EXCEPT their final
# step (sigma_next == 0 takes the single-call Euler fallback), so their
# exact total is 2*steps - 1 — an exact total keeps the progress bar from
# stalling one call short of 100% until finish() clamps it.
_SECOND_ORDER = {"heun", "dpmpp_sde", "res_2s", "res_2s_ancestral"}


def calls_per_step(sampler: str) -> int:
    return 2 if sampler in _SECOND_ORDER else 1


def total_calls(sampler: str, steps: int) -> int:
    if sampler in _SECOND_ORDER:
        return max(1, 2 * steps - 1)
    return steps


def segment_calls(sampler: str, start: int, length: int, steps: int) -> int:
    """Model calls of ladder steps ``[start, start + length)`` of a
    ``steps``-step run: what one segment's event stands for. Summed over
    the segments of a run it is ``total_calls``."""
    calls = calls_per_step(sampler) * length
    if sampler in _SECOND_ORDER and start + length >= steps:
        calls -= 1                 # the final step's single-call fallback
    return calls


# the (traced) global ladder index of the sampler step being traced, set by
# the sampler scans; None outside one (python ladders, a bare denoiser)
_STEP: contextvars.ContextVar = contextvars.ContextVar(
    "cdt_progress_step", default=None)


@contextlib.contextmanager
def sampler_step(index):
    """Entered by ``samplers.run_program``/``run_segment`` around one
    ladder step: lets a wrapped denoiser called inside it (in a branch of
    the step too) know which step it serves."""
    reset = _STEP.set(index)
    try:
        yield
    finally:
        _STEP.reset(reset)


def wrap_denoiser(denoise, token, shard_index):
    """Interpose on a denoiser: after a model call, stream the current x0
    estimate (first batch element) to the host sink. ``token`` may be
    traced: an int32 scalar (every call reports itself), or ``[token,
    stride]`` — then, inside a sampler scan, only the calls of every
    stride-th step report, each for ``stride`` calls, and the chip meets
    the host that much less often. ``shard_index`` may be a traced
    ``axis_index`` under ``shard_map`` (each shard reports itself — the
    sink keys previews by shard and counts steps on shard 0 only)."""

    def wrapped(x, sigma):
        x0 = denoise(x, sigma)
        tok = jnp.asarray(token, jnp.int32)
        step = _STEP.get()
        if tok.ndim == 0 or step is None:
            jax.debug.callback(_dispatch, tok.reshape(-1)[0], shard_index,
                               sigma, x0[:1])
            return x0
        stride = jnp.maximum(tok[1], 1)
        jax.lax.cond(
            (step + 1) % stride == 0,
            lambda: jax.debug.callback(
                _dispatch, jnp.stack([tok[0], stride]), shard_index, sigma,
                x0[:1]),
            lambda: None)
        return x0

    return wrapped


# --- the served lanes: progress as a program output, no callback -------------


class DenoiserTap:
    """Interpose on a denoiser WITHOUT a host effect: remember the first
    call a sampler step makes — ``(sigma, x0[:1])``, traced at the step's
    own level (every sampler's first call is; a second-order corrector
    inside a ``cond`` is not, and is never kept) — for the scan that runs
    the step to emit as its per-step output
    (``samplers.run_segment(..., tap=)``). The program's last row is the
    preview the host reads when the segment is over."""

    def __init__(self, denoise):
        self._denoise = denoise
        self._seen = None

    def __call__(self, x, sigma):
        x0 = self._denoise(x, sigma)
        if self._seen is None:
            with device_scope("sampler"):
                self._seen = (jnp.asarray(sigma, jnp.float32), x0[:1])
        return x0

    def take(self):
        """The call kept since the last ``take`` (None if none)."""
        seen, self._seen = self._seen, None
        return seen


def deliver_segment(on_step, sigma, previews, calls: int) -> None:
    """Hand a finished segment's tap to the host-side reporter
    ``on_step(sigma, x0, calls=, shard=)`` (``_ProgressScope.on_step`` →
    ``ProgressTracker.report``): ``previews`` is the program's output, one
    latent a dp shard ([n_dp, ...]), fetched here — the program is over,
    so this waits on a copy and on no chip. Timed like a callback:
    ``progress.sink`` span, ``cdt_progress_callback_seconds``."""
    with _spans.timed_span("progress.sink", _tm.PROGRESS_CALLBACK_SECONDS,
                           source="segment"):
        sigma, previews = float(sigma), np.asarray(previews)
        if _tm_enabled():
            _tm.PROGRESS_EVENTS.labels(source="segment").inc(
                previews.shape[0])
        for shard in range(previews.shape[0]):
            on_step(sigma, previews[shard:shard + 1], calls=calls,
                    shard=shard)
