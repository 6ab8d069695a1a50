"""Disaggregated stage-split serving (ROADMAP item 3, docs/stages.md).

The fused serving path runs every front-door group end-to-end on the one
graph-exec thread: prefix (checkpoint load + text encode), the
microbatched sampler program, VAE decode, suffix. Only the sampler loop
drives the mesh at full MFU — encode and decode are cheap, bursty, and
batchable (``docs/pp-memo.md``), yet they serialize with the denoise
program and hold its queue slot.

This package splits the pipeline into three independently scaled stage
pools behind the existing front door:

- **encode pool** (N host threads): each member's graph prefix — model
  resolve, text encode through the PR 8 conditioning cache (each unique
  prompt encodes once fleet-wide), sampler-input resolution, and the
  completed-result cache probe. Pure host + encoder work.
- **denoise pool** (exactly ONE worker — it owns the mesh): the
  microbatched *latent* program
  (``diffusion/pipeline.latent_microbatch_fn`` — the fused program
  stopped at ``x0``, same unrolled per-request subgraphs). The prompt
  queue's slot frees when this stage finishes, so the next group's
  denoise starts while the previous group decodes.
- **decode pool** (M host threads): coalesces latents across concurrent
  requests into shape buckets and decodes each bucket as ONE batched
  VAE program (``decode_latents``), then runs each member's suffix.

Stage handoffs are :class:`~.latents.LatentHandoff`\\ s — the checksummed
npz wire format (``diffusion/checkpoint.py`` contract). In-process the
decode pool reads the denoise program's device array directly; the
transfer (device→host materialization, plus the full wire round trip
under ``CDT_STAGE_WIRE=1``) happens on the decode worker WHILE the
denoise pool dispatches its next program — the T3-style
compute/transfer overlap (PAPERS.md).

Bit-identity: every stage boundary is a pure program split on
already-materialized values (the PR 14 seg/fin precedent), so the
staged path's outputs are bit-identical to the fused path's — proven,
not approximate (``tests/test_stages_equivalence.py``). ``CDT_STAGES=0``
removes the subsystem and restores the fused path verbatim.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np

from ... import telemetry
from ...telemetry import metrics as _tm
from ...utils import constants
from ...utils.logging import debug_log, log
from ...lint.lockorder import tracked_lock
from .latents import LatentHandoff, LatentWireError
from .pool import StagePool, StageWorkerDeath

__all__ = ["StageManager", "StagePool", "StageWorkerDeath",
           "LatentHandoff", "LatentWireError", "build_stages",
           "stages_enabled"]


def stages_enabled() -> bool:
    return constants.STAGES.get()


class _EncodeWork:
    __slots__ = ("ticket", "member", "redispatch", "done")

    def __init__(self, ticket, member):
        self.ticket = ticket
        self.member = member
        self.redispatch = 0
        self.done = False

    def fail(self, manager, status: str, error: str = "") -> None:
        self.done = True
        entry = {"status": status}
        if error:
            entry["error"] = error
        manager._complete(self.ticket, self.member, entry)
        # a failed encode item still counts toward the group's encode
        # barrier — without this the denoise stage never dispatches and
        # the queue consumer awaits denoise_done forever
        manager._after_encode(self.ticket)


class _DenoiseWork:
    __slots__ = ("ticket", "redispatch", "done")

    def __init__(self, ticket):
        self.ticket = ticket
        self.redispatch = 0
        self.done = False

    def fail(self, manager, status: str, error: str = "") -> None:
        self.done = True
        for p in self.ticket.take_ready():
            entry = {"status": status}
            if error:
                entry["error"] = error
            manager._complete(self.ticket, p.member, entry)
        self.ticket.resolve_denoise()


class _DecodeWork:
    __slots__ = ("ticket", "p", "latents", "np_latents", "sampler_batch",
                 "redispatch", "done")

    def __init__(self, ticket, prepared, latents, sampler_batch: int):
        self.ticket = ticket
        self.p = prepared
        self.latents = latents          # device array until transferred
        self.np_latents = None
        self.sampler_batch = sampler_batch
        self.redispatch = 0
        self.done = False

    def bucket_key(self) -> tuple:
        from ...diffusion.pipeline import mesh_cache_key

        return (id(self.p.pipeline), mesh_cache_key(self.p.mesh),
                tuple(self.latents.shape))

    def handoff(self) -> LatentHandoff:
        p = self.p
        return LatentHandoff(
            prompt_id=p.member.prompt_id,
            latents=np.asarray(self.latents),
            meta={"model": getattr(getattr(p.model, "preset", None),
                                   "name", None),
                  "height": p.spec.height, "width": p.spec.width,
                  "steps": p.spec.steps, "seed": p.seed,
                  "fingerprint": p.member.fingerprint})

    def fail(self, manager, status: str, error: str = "") -> None:
        self.done = True
        entry = {"status": status}
        if error:
            entry["error"] = error
        manager._complete(self.ticket, self.p.member, entry)


class _GroupTicket:
    """One front-door batch job moving through the stages."""

    def __init__(self, manager, job, members, sampler_node_ids, context,
                 loop, denoise_done, record):
        self.manager = manager
        self.job = job
        self.members = list(members)
        self.sampler_node_ids = dict(sampler_node_ids)
        self.context = context
        self.loop = loop
        self.denoise_done = denoise_done
        self.record = record
        self.pending = len(self.members)
        self.encode_left = len(self.members)
        self.ready: list = []
        self._lock = tracked_lock("stage.ticket")
        self._denoise_resolved = False

    def add_ready(self, prepared) -> None:
        with self._lock:
            self.ready.append(prepared)

    def take_ready(self) -> list:
        with self._lock:
            out, self.ready = self.ready, []
        return out

    def member_done(self) -> bool:
        """Decrement the outstanding-member count; True when this was
        the last one (the runtime observes end-to-end duration then)."""
        with self._lock:
            self.pending -= 1
            return self.pending <= 0

    def encode_done(self) -> "tuple[bool, bool]":
        with self._lock:
            self.encode_left -= 1
            return self.encode_left <= 0, bool(self.ready)

    def resolve_denoise(self) -> None:
        """Free the mesh: tell the runtime the denoise stage is done
        with this group so the queue dispatches the next job while the
        decode pool finishes this one. Idempotent."""
        with self._lock:
            if self._denoise_resolved:
                return
            self._denoise_resolved = True
        self.manager._marshal(self.loop, _resolve, self.denoise_done)


def _resolve(fut) -> None:
    if not fut.done():
        fut.set_result(None)


class StageManager:
    """The three stage pools bound to one controller.

    Built by the controller under ``CDT_STAGES=1`` and attached to the
    prompt queue (``queue.stages``); the queue's consumer routes batch
    jobs here and awaits only the denoise stage before freeing its
    slot. Pools are per-controller, threads are daemons, and nothing
    starts until the first staged group arrives."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self.base_encode = max(1, constants.STAGE_ENCODE_WORKERS.get())
        self.base_decode = max(1, constants.STAGE_DECODE_WORKERS.get())
        self.encode = StagePool("encode", self.base_encode,
                                self._run_encode, steal=self._pick_steal,
                                redispatch=self._redispatch_encode,
                                clock=clock)
        # exactly one denoise worker: one mesh, one program at a time —
        # the stage split raises the work per program and what runs
        # AROUND the mesh, never the number of concurrent mesh programs
        self.denoise = StagePool("denoise", 1, self._run_denoise,
                                 clock=clock)
        self.decode = StagePool(
            "decode", self.base_decode, self._run_decode,
            # duck-typed so tests can drive the pool with fake items
            batch_key=lambda item: item.bucket_key(),
            max_batch=constants.STAGE_DECODE_BATCH.get(),
            window_s=constants.STAGE_DECODE_WINDOW_MS.get() / 1000.0,
            steal=self._pick_steal,
            redispatch=self._redispatch_decode, clock=clock)
        # chaos hook: called with the picked decode batch right after
        # transfer, while the worker "holds" the latents (may raise
        # StageWorkerDeath — tests/test_stages.py)
        self._death_hook: Optional[Callable[[list], None]] = None
        self.counts = {"groups": 0, "members": 0, "cache_hits": 0,
                       "fallbacks": 0, "redispatched": 0}
        self._counts_lock = tracked_lock("stage.counts")

    # --- the front half: runtime integration --------------------------------

    def eligible(self, job) -> bool:
        """Only front-door batch jobs ride the stages: solo prompts keep
        the fused path (preemption, progress streaming, ControlNet).
        ``cache: "near"`` members also keep the fused path — the near
        tier's donor/serve machinery (cluster/cache/fleet.py) rides the
        fused preemptible sampler, which has no stage-split analogue."""
        group = getattr(job, "group", None)
        if group is None:
            return False
        return not any(getattr(m, "cache_mode", "use") == "near"
                       for m in group)

    def submit_group(self, job, members, sampler_node_ids, context, loop,
                     denoise_done, record) -> None:
        """Enter one batch job into the encode pool. ``record(member,
        entry, last)`` is invoked ON ``loop`` as each member reaches a
        terminal state; ``denoise_done`` resolves when the mesh is free
        for the next job."""
        ticket = _GroupTicket(self, job, members, sampler_node_ids,
                              context, loop, denoise_done, record)
        with self._counts_lock:
            self.counts["groups"] += 1
            self.counts["members"] += len(ticket.members)
        self.rebalance()
        for m in ticket.members:
            self.encode.put(_EncodeWork(ticket, m))

    def depth(self) -> int:
        """Host-side stage backlog (encode + decode; the denoise queue
        is bounded by the prompt queue itself). Feeds the front door's
        admission depth so freeing queue slots at denoise-done cannot
        admit unbounded work that piles up in decode."""
        return self.encode.depth() + self.decode.depth()

    def depths(self) -> dict:
        return {"encode": self.encode.depth(),
                "denoise": self.denoise.depth(),
                "decode": self.decode.depth()}

    def overloaded(self) -> "str | None":
        """Stage name whose backlog exceeds CDT_STAGE_SHED_DEPTH (the
        load_smoke --stages assertion), or None."""
        shed = constants.STAGE_SHED_DEPTH.get()
        for name, d in self.depths().items():
            if d > shed:
                return name
        return None

    def stop(self) -> None:
        for pool in (self.encode, self.denoise, self.decode):
            for item in pool.stop():
                try:
                    item.fail(self, "interrupted")
                except Exception as e:  # noqa: BLE001 — shutdown barrier
                    debug_log(f"stages: drop at shutdown failed: {e!r}")

    # --- per-pool scaling ----------------------------------------------------

    def rebalance(self) -> None:
        """Size each host-side pool on ITS OWN queue depth — the
        per-pool half of the autoscaler split (the fleet autoscaler
        sizes chips on denoise-facing signals only; docs/stages.md).
        Deterministic: grow by one past CDT_STAGE_SCALE_DEPTH items per
        worker, shrink back to the configured base when idle."""
        per = constants.STAGE_SCALE_DEPTH.get()
        ceiling = constants.STAGE_MAX_WORKERS.get()
        for pool, base in ((self.encode, self.base_encode),
                           (self.decode, self.base_decode)):
            depth = pool.depth()
            if depth > per * pool.workers and pool.workers < ceiling:
                log(f"stages: {pool.name} pool {pool.workers} -> "
                    f"{pool.workers + 1} (depth {depth})")
                pool.resize(pool.workers + 1)
            elif depth == 0 and pool.busy == 0 and pool.workers > base:
                pool.resize(pool.workers - 1)

    def _pick_steal(self, pool) -> Optional[StagePool]:
        """Cross-stage steal victim for an idle host-side worker: the
        deepest sibling stage queue (the PR 7 most-starved-first idiom
        across stages). The denoise pool is never a victim or a thief —
        it owns the mesh."""
        if not constants.STAGE_STEAL.get():
            return None
        sibs = [p for p in (self.encode, self.decode) if p is not pool]
        victim = max(sibs, key=lambda p: p.depth(), default=None)
        if victim is None or victim.depth() == 0:
            return None
        return victim

    # --- encode stage --------------------------------------------------------

    def _run_encode(self, works: list) -> None:
        for w in works:
            self._encode_member(w)
            w.done = True

    def _encode_member(self, w: _EncodeWork) -> None:
        from ..frontdoor.microbatch import _prepare, _serve_cached

        ticket, member = w.ticket, w.member
        cache = ticket.context.get("content_cache")
        # the WHOLE member (prefix, cache probe, cached suffix) runs
        # inside one isolation barrier and the encode barrier advances
        # in a finally: an escaping exception here would otherwise be
        # swallowed by the pool's runner barrier with the group's
        # denoise_done future never resolving — wedging the queue
        # consumer for the life of the process
        try:
            ev = ticket.context.get("interrupt_event")
            if ev is not None and ev.is_set():
                self._complete(ticket, member, {"status": "interrupted"})
                return
            p = _prepare(member, ticket.sampler_node_ids[member.prompt_id],
                         ticket.context)
            results: dict = {}
            if _serve_cached(p, cache, results):
                # completed-result tier answered in the ENCODE stage —
                # the request never touches the mesh at all. The probe
                # inside _serve_cached walks the full fleet ladder
                # (local memory → disk → ring owner), so a remote shard
                # hit also resolves here, before any pool hand-off.
                with self._counts_lock:
                    self.counts["cache_hits"] += 1
                self._complete(ticket, member, results[member.prompt_id])
                return
            if cache is not None and member.fingerprint is not None:
                cache.record_request(hit=False)
            ticket.add_ready(p)
        except InterruptedError:
            self._complete(ticket, member, {"status": "interrupted"})
        except Exception as e:  # noqa: BLE001 — member isolation barrier
            log(f"stages: encode failed for {member.prompt_id}: {e}")
            self._complete(ticket, member,
                           {"status": "error", "error": str(e)})
        finally:
            self._after_encode(ticket)

    def _after_encode(self, ticket: _GroupTicket) -> None:
        done, has_ready = ticket.encode_done()
        if not done:
            return
        if has_ready:
            self.denoise.put(_DenoiseWork(ticket))
        else:
            # every member answered (cache/error) without the mesh
            ticket.resolve_denoise()

    # --- denoise stage -------------------------------------------------------

    def _run_denoise(self, works: list) -> None:
        for w in works:
            try:
                self._denoise_ticket(w.ticket)
            finally:
                w.done = True
                w.ticket.resolve_denoise()

    def _denoise_ticket(self, ticket: _GroupTicket) -> None:
        prepared = ticket.take_ready()
        if not prepared:
            return
        # sub-group by runtime signature exactly like the fused path;
        # the staged lane additionally needs the latent entry points
        groups: dict[tuple, list] = {}
        singles: list = []
        for p in prepared:
            if p.stackable and hasattr(p.pipeline, "generate_latents") \
                    and hasattr(p.pipeline, "decode_latents"):
                groups.setdefault(p.signature(), []).append(p)
            else:
                singles.append(p)
        for p in singles:
            # non-stackable members (control conditioning, no mesh,
            # unsupported pipeline) run the fused solo path on the
            # denoise worker — they hold the mesh anyway
            if telemetry.enabled():
                _tm.BATCH_SIZE.observe(1)
            self._solo_member(ticket, p, batch_size=1)
        for sig, grp in groups.items():
            self._denoise_subgroup(ticket, grp)

    def _denoise_subgroup(self, ticket: _GroupTicket, grp: list) -> None:
        from ..residency import pinned_bundle

        lead = grp[0]
        try:
            with pinned_bundle(lead.model):
                lats = lead.pipeline.generate_latents(
                    lead.mesh, lead.spec,
                    seeds=[p.seed for p in grp],
                    contexts=[p.context for p in grp],
                    uncond_contexts=[p.uncond for p in grp],
                    ys=[p.y for p in grp], uys=[p.uy for p in grp],
                )
            if telemetry.enabled():
                _tm.BATCH_SIZE.observe(len(grp))
        except InterruptedError:
            for p in grp:
                self._complete(ticket, p.member,
                               {"status": "interrupted"})
            return
        except Exception as e:  # noqa: BLE001 — fall back, never lose jobs
            log(f"stages: latent microbatch of {len(grp)} failed ({e}); "
                f"falling back to fused solo execution")
            if telemetry.enabled():
                _tm.BATCH_FALLBACKS.inc()
            with self._counts_lock:
                self.counts["fallbacks"] += 1
            for p in grp:
                if telemetry.enabled():
                    _tm.BATCH_SIZE.observe(1)
                self._solo_member(ticket, p, batch_size=1)
            return
        from ..frontdoor.microbatch import _observe_group_shape

        _observe_group_shape(lead, len(grp))
        # the handoff carries the LAZY device arrays: materialization
        # happens on the decode worker, overlapped with this pool's
        # next program (T3-style; docs/stages.md). One put for the group:
        # its latents share a decode program however the threads are
        # scheduled
        self.decode.put(*[_DecodeWork(ticket, p, lat, sampler_batch=len(grp))
                          for p, lat in zip(grp, lats)])

    def _solo_member(self, ticket: _GroupTicket, p,
                     batch_size: int = 1) -> None:
        """The fused pass-through: the sampler node's own execute +
        suffix, byte-for-byte the solo queue path (shared helpers with
        the fused group executor)."""
        from ..frontdoor.microbatch import _fill_cache, _finish, _solo

        cache = ticket.context.get("content_cache")
        try:
            images = _solo(p)
            _fill_cache(p, cache, images)
            out_cache = _finish(p, images)
            self._complete(ticket, p.member,
                           {"status": "success", "outputs": out_cache,
                            "batch_size": batch_size})
        except InterruptedError:
            self._complete(ticket, p.member, {"status": "interrupted"})
        except Exception as e:  # noqa: BLE001 — member isolation barrier
            log(f"stages: solo member {p.member.prompt_id} failed: {e}")
            self._complete(ticket, p.member,
                           {"status": "error", "error": str(e)})

    # --- decode stage --------------------------------------------------------

    def _run_decode(self, works: list) -> None:
        live: list[_DecodeWork] = []
        for w in works:
            ev = w.ticket.context.get("interrupt_event")
            if ev is not None and ev.is_set():
                w.done = True
                self._complete(w.ticket, w.p.member,
                               {"status": "interrupted"})
            else:
                live.append(w)
        if not live:
            return
        ready: list[_DecodeWork] = []
        for w in live:
            # per-member transfer isolation: a wire-format failure
            # (checksum mismatch, unserializable meta under
            # CDT_STAGE_WIRE=1) must error THAT member terminally, not
            # strand the whole batch without history entries
            try:
                self._transfer(w)
            except Exception as e:  # noqa: BLE001 — member isolation
                log(f"stages: latent transfer failed for "
                    f"{w.p.member.prompt_id}: {e}")
                w.done = True
                self._complete(w.ticket, w.p.member,
                               {"status": "error", "error": str(e)})
            else:
                ready.append(w)
        live = ready
        if not live:
            return
        hook = self._death_hook
        if hook is not None:
            hook(live)              # chaos: may raise StageWorkerDeath
        lead = live[0].p
        from ..residency import pinned_bundle

        try:
            with pinned_bundle(lead.model):
                images = lead.pipeline.decode_latents(
                    lead.mesh, [w.np_latents for w in live],
                    per_device_batch=lead.spec.per_device_batch)
            if telemetry.enabled():
                _tm.DECODE_BATCH_SIZE.observe(len(live))
        except StageWorkerDeath:
            raise
        except InterruptedError:
            for w in live:
                w.done = True
                self._complete(w.ticket, w.p.member,
                               {"status": "interrupted"})
            return
        except Exception as e:  # noqa: BLE001 — fall back per item
            log(f"stages: batched decode of {len(live)} failed ({e}); "
                f"decoding solo")
            for w in live:
                self._decode_solo(w)
            return
        for w, img in zip(live, images):
            self._finish_member(w, img, decode_batch=len(live))

    def _transfer(self, w: _DecodeWork) -> None:
        """Materialize one handoff on the decode side. Under
        ``CDT_STAGE_WIRE=1`` the latent makes the full checksummed wire
        round trip (serialize → sha256 → parse → verify) — the
        cross-worker transport path, validated on every handoff."""
        if w.np_latents is not None:
            return
        # transfer telemetry only — never feeds the program
        t0 = time.perf_counter()
        if constants.STAGE_WIRE.get():
            arr = np.asarray(
                LatentHandoff.from_payload(w.handoff().to_payload())
                .latents)
        else:
            arr = np.asarray(w.latents)
        w.np_latents = arr
        if telemetry.enabled():
            _tm.LATENT_TRANSFER_BYTES.observe(arr.nbytes)
            _tm.LATENT_TRANSFER_SECONDS.observe(time.perf_counter() - t0)

    def _decode_solo(self, w: _DecodeWork) -> None:
        """Decode one latent in its own (batch-of-1) program — the
        fallback when a batched decode program fails; the member's
        admitted work must never be lost to batching."""
        from ..residency import pinned_bundle

        try:
            with pinned_bundle(w.p.model):
                images = w.p.pipeline.decode_latents(
                    w.p.mesh, [w.np_latents],
                    per_device_batch=w.p.spec.per_device_batch)
            if telemetry.enabled():
                _tm.DECODE_BATCH_SIZE.observe(1)
        except Exception as e:  # noqa: BLE001 — member isolation barrier
            log(f"stages: solo decode failed for "
                f"{w.p.member.prompt_id}: {e}")
            w.done = True
            self._complete(w.ticket, w.p.member,
                           {"status": "error", "error": str(e)})
            return
        self._finish_member(w, images[0], decode_batch=1)

    def _finish_member(self, w: _DecodeWork, images,
                       decode_batch: int) -> None:
        from ..frontdoor.microbatch import _fill_cache, _finish

        w.done = True
        cache = w.ticket.context.get("content_cache")
        try:
            _fill_cache(w.p, cache, images)
            out_cache = _finish(w.p, images)
        except InterruptedError:
            self._complete(w.ticket, w.p.member,
                           {"status": "interrupted"})
            return
        except Exception as e:  # noqa: BLE001 — member isolation barrier
            log(f"stages: suffix failed for {w.p.member.prompt_id}: {e}")
            self._complete(w.ticket, w.p.member,
                           {"status": "error", "error": str(e)})
            return
        self._complete(w.ticket, w.p.member,
                       {"status": "success", "outputs": out_cache,
                        "batch_size": w.sampler_batch,
                        "decode_batch": decode_batch})

    def _redispatch_decode(self, items: list) -> None:
        self._redispatch(self.decode, items)

    def _redispatch_encode(self, items: list) -> None:
        self._redispatch(self.encode, items)

    def _redispatch(self, pool: StagePool, items: list) -> None:
        """Bounded re-dispatch of a dead worker's held items to a
        surviving (or respawned) worker. Intentional-departure
        semantics: no dead-letter, no breaker evidence — past the bound
        the member errors LOUDLY instead of ping-ponging."""
        bound = constants.STAGE_MAX_REDISPATCH.get()
        for item in items:
            if getattr(item, "done", False):
                # already terminal (interrupted/errored before the
                # death) — re-dispatching would double-complete it
                continue
            item.redispatch += 1
            if item.redispatch > bound:
                item.fail(self, "error",
                          f"stage worker died {item.redispatch} times "
                          f"holding this item — redispatch bound "
                          f"({bound}) exceeded")
                continue
            with self._counts_lock:
                self.counts["redispatched"] += 1
            pool.put(item)

    # --- completion plumbing -------------------------------------------------

    def _complete(self, ticket: _GroupTicket, member, entry: dict) -> None:
        last = ticket.member_done()
        self._marshal(ticket.loop, ticket.record, member, entry, last)

    @staticmethod
    def _marshal(loop, fn, *args) -> None:
        """Run ``fn`` on the controller's event loop; if the loop is
        already closed (shutdown teardown) run inline so terminal state
        still lands."""
        try:
            loop.call_soon_threadsafe(fn, *args)
        except RuntimeError:
            try:
                fn(*args)
            except Exception as e:  # noqa: BLE001 — teardown barrier
                debug_log(f"stages: inline completion failed: {e!r}")

    # --- introspection -------------------------------------------------------

    def stats(self) -> dict:
        with self._counts_lock:
            counts = dict(self.counts)
        return {
            "enabled": True,
            "pools": {p.name: p.stats()
                      for p in (self.encode, self.denoise, self.decode)},
            "wire": constants.STAGE_WIRE.get(),
            "steal": constants.STAGE_STEAL.get(),
            "decode_batch_max": self.decode.max_batch,
            "decode_window_ms": self.decode.window_s * 1000.0,
            **counts,
        }


def build_stages() -> Optional[StageManager]:
    """Controller hook: the stage manager, or None under CDT_STAGES=0
    (the fused path runs verbatim)."""
    if not stages_enabled():
        log("stage-split serving disabled (CDT_STAGES=0) — fused path")
        return None
    return StageManager()
