"""Persistent XLA compilation cache: the ONE place its directory is chosen.

Full-scale programs here are expensive to compile — the SDXL sampler
programs take minutes on a v5e, and the offloaded one-jit ladders
(``diffusion/offload.py``) retrace per sigma-ladder LENGTH, so a user
changing ``steps`` from 30 to 25 pays a fresh full-model compile.
Everything that compiles goes through :func:`enable_compile_cache`: the
server at boot, ``bench.py``, ``scripts/mfu_probe.py``, the warmup pass
(``diffusion/warmup.py``) and the test suite.

A cached executable keeps the metadata it was compiled with, so the
key includes it (``jax_compilation_cache_include_metadata_in_key``, with
the one line an operation is traced at and none of its callers): what a
profile names an operation is then what the running code names it.

The directory is placed from outside with JAX's own variable,
``JAX_COMPILATION_CACHE_DIR``. Where it is set, JAX reads it itself and
this module sets no directory in code. Where it is not, the directory is
``<checkout>/.cache/xla``, resolved from this file: the path is part of
nothing's identity but its own, so it must not depend on ``$HOME``, a
temporary directory, a pid or the time — a cache that moves never hits.
The shape catalog and the attention tuning overlay live beside it
(:func:`cache_dir_default`).

Reference analogue: ComfyUI relies on torch CUDA kernels being
pre-built, so its server has no compile-latency problem to manage; an
XLA-based server does, and this is the standard jax answer.
"""

from __future__ import annotations

import os
import tempfile
from pathlib import Path
from typing import Optional

from .logging import log

JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = Path(__file__).resolve().parents[2]
_DEFAULT = str(_CHECKOUT / ".cache" / "xla")

# directory of the last enable_compile_cache call — the warmup pass,
# system_info and telemetry read it instead of re-deriving the rule
_active: Optional[str] = None
_listening = False


def cache_dir_default() -> str:
    """The directory ``enable_compile_cache()`` resolves to, WITHOUT
    enabling anything — the shape catalog and the tuning overlay persist
    next to it whether or not this process compiles."""
    return os.environ.get(JAX_CACHE_ENV) or _DEFAULT


def active_cache_dir() -> Optional[str]:
    """Directory the live jax process is caching into (None before
    ``enable_compile_cache`` ran)."""
    return _active


def enable_compile_cache(path: Optional[str] = None,
                         min_compile_secs: float = 1.0) -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set: jax reads the
    variable itself, so no directory is set in code. Otherwise the
    directory is ``path`` (the test suite keeps its CPU artifacts in a
    directory of its own) or ``<checkout>/.cache/xla``. A directory that
    cannot be created or written raises ``OSError``: a server that would
    silently recompile everything on each start says so at boot.

    ``min_compile_secs``: persistence threshold. The server default
    (1.0 s) skips trivial programs; bench and warmup pass 0.0 so every
    program lands on disk.
    """
    global _active
    import jax

    d = os.environ.get(JAX_CACHE_ENV)
    placed_outside = bool(d)
    if not placed_outside:
        d = path or _DEFAULT
    os.makedirs(d, exist_ok=True)
    with tempfile.TemporaryFile(dir=d):     # raises where d is read-only
        pass
    if not placed_outside:
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    # An executable read from the cache carries the names it was COMPILED
    # with. JAX's default key strips debug info, so a program whose
    # operations did not change is served with the op_name and source
    # lines of whichever checkout compiled it first, and a profile shows
    # stale (or no) cdt.<layer> scopes (telemetry/device_scopes.py; seen on
    # the chip in PR 34: fin_body read from PR 33's cache had none). With
    # the names in the key a profile's names are the running code's; the
    # price is that a program is compiled again after an edit that moves
    # the lines it is traced through, once a checkout.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # ... and only the line an operation is traced AT, not the ten frames
    # of callers JAX adds by default: the warm-up pass and a request reach
    # one program by different paths and must find one cache entry. (Not
    # jax_include_full_tracebacks_in_locations=False: that path of JAX
    # 0.9 drops most of the names themselves; seen on the chip, PR 34.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    _active = d
    log(f"compile cache: persisting XLA programs under {d}"
        + (f" (from {JAX_CACHE_ENV})" if placed_outside else ""))
    from ..telemetry import enabled as _tm_enabled
    from ..telemetry import metrics as _tm

    if _tm_enabled():
        _tm.COMPILE_CACHE_ENABLED.set(1.0)
        _count_compiles()
    return d


def _count_compiles() -> None:
    """Feed jax's own compile and cache events into the metrics registry
    (once per process): whether a restart found its programs on disk is
    then a number in ``/distributed/metrics.json``, not a guess from
    directory listings — and so is WHICH program it did not find, and
    what each program's tracing, lowering, cache read or compile took,
    who asked for it and when (``telemetry/build.py``: the set-up ledger,
    and the three listeners)."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    from ..telemetry import build

    jax.monitoring.register_event_listener(build.on_event)
    jax.monitoring.register_event_duration_secs_listener(build.on_duration)
    jax.monitoring.register_event_time_span_listener(build.on_time_span)
