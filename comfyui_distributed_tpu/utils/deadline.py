"""Deadline-guarded device-backend queries for the control plane.

``jax.devices()`` and per-device ``memory_stats()`` are synchronous calls
into the device runtime. The chip is local and owned by this process, so
they normally answer at once — but the first one initialises the backend,
and a runtime in trouble can block. An aiohttp route that makes such a
call inline freezes the whole event loop, including
``/distributed/health``, the exact endpoint peers use to decide this host
is dead. Reference analogue for the *shape* of the guard: its worker
probes use bounded HTTP timeouts everywhere (``utils/network.py``); the
device backend gets the same discipline. The info routes turn a stall or
a failure into a 503 (``api/info_routes.py``).

Leak discipline: a stalled call can never be cancelled, so each timeout
occupies its thread for as long as the stall lasts. Queries run on
dedicated **daemon** threads (never the shared default executor — worker
launch, the Cloudflare tunnel set-up, and media hashing live there)
behind a 2-permit semaphore: at most TWO threads can ever be stuck,
further calls fall back immediately, and interpreter shutdown is never
blocked. A cooldown gate additionally short-circuits attempts after a
stall.

Exceptions are NOT conflated with stalls: a query that *fails fast*
(e.g. a backend raising at init) propagates to the caller and does not
close the gate.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Callable

from .logging import log

_blocked_until = 0.0
_inflight = threading.Semaphore(2)


def gate_open() -> bool:
    return time.monotonic() >= _blocked_until


def _note_stall(cooldown_s: float) -> None:
    global _blocked_until
    _blocked_until = time.monotonic() + cooldown_s


def reset_gate() -> None:
    """Test hook / manual recovery."""
    global _blocked_until
    _blocked_until = 0.0
    # NOTE: permits held by genuinely-stuck threads are unrecoverable by
    # design (the thread itself must finish to release)


async def deadline_call(fn: Callable[[], Any], timeout_s: float = 5.0,
                        cooldown_s: float = 120.0,
                        fallback: Any = None) -> Any:
    """Run a (possibly-hanging) device-backend query off the event loop
    with a deadline.

    - timeout → log, close the gate for ``cooldown_s``, return
      ``fallback`` (the thread stays parked until the call returns);
    - gate closed or both leak permits consumed → ``fallback``
      immediately;
    - ``fn`` raises → the exception PROPAGATES (fast failures carry
      real diagnostics; only stalls degrade)."""
    if not gate_open():
        return fallback
    if not _inflight.acquire(blocking=False):
        return fallback
    loop = asyncio.get_running_loop()
    fut: asyncio.Future = loop.create_future()

    def deliver(cb):
        try:
            loop.call_soon_threadsafe(cb)
        except RuntimeError:
            pass      # loop already closed — a freed stale thread's
                      # result has nowhere to go, and that's fine

    def runner():
        try:
            result = fn()
        except BaseException as e:  # noqa: BLE001 — delivered, not dropped
            # bind NOW: CPython clears the except-variable at block
            # exit, racing the scheduled callback (a bare closure over
            # `e` intermittently dies with NameError and the failure
            # would misclassify as a stall)
            deliver(lambda exc=e: fut.set_exception(exc)
                    if not fut.done() else None)
        else:
            deliver(lambda: fut.set_result(result)
                    if not fut.done() else None)
        finally:
            _inflight.release()

    threading.Thread(target=runner, daemon=True,
                     name="cdt-device-query").start()
    try:
        return await asyncio.wait_for(fut, timeout=timeout_s)
    except asyncio.TimeoutError:
        _note_stall(cooldown_s)
        log(f"device backend unresponsive (> {timeout_s:.0f}s) — "
            f"refusing device queries for {cooldown_s:.0f}s")
        return fallback
