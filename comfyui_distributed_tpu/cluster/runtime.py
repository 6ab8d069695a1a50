"""Host controller runtime: the local prompt queue + execution context.

The reference relies on ComfyUI's PromptServer queue + executor
(``utils/async_helpers.py:108-149`` pushes into ``prompt_queue``). This is
the standalone equivalent: an asyncio consumer that validates prompts,
executes them in a worker thread (JAX compute must not block the loop),
and exposes ``queue_remaining`` for health probes — the field the
reference's least-busy scheduler reads (``dispatch.py:225-268``).

Two job shapes ride the same queue: classic solo prompts, and *batch
jobs* from the serving front door (``cluster/frontdoor``) — N coalesced
member prompts executed as one unit with a shared microbatched sampler
program. Either way execution is serialized per controller (one mesh,
one program at a time); batching raises the work per program, not the
number of concurrent programs.
"""

from __future__ import annotations

import asyncio
import contextvars
import dataclasses
import secrets
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional

from .. import telemetry
from ..graph.executor import GraphExecutor, strip_meta, validate_prompt
from ..telemetry import metrics as _tm
from ..utils import constants
from ..utils.exceptions import ValidationError
from ..utils.logging import log, trace_info


@dataclasses.dataclass
class PromptJob:
    prompt_id: str
    prompt: dict
    client_id: str = ""
    trace_id: str | None = None
    # master-side dispatch span id carried by X-CDT-Trace: the execution
    # span parents onto it so cross-host traces stitch (telemetry/spans)
    parent_span_id: str | None = None
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    future: Optional[asyncio.Future] = None
    # --- serving front door metadata (cluster/frontdoor) -------------------
    tenant: str = constants.DEFAULT_TENANT
    priority: str = constants.DEFAULT_PRIORITY
    # monotonic deadline; a job still queued past it is recorded
    # "expired" instead of executed (the client asked for freshness)
    deadline_at: float | None = None
    # batch jobs: the coalesced member jobs (each with its own prompt_id/
    # deadline) and each member's sampler node id. ``prompt`` is unused.
    group: "list[PromptJob] | None" = None
    sampler_node_ids: dict | None = None
    # --- content cache (cluster/cache, docs/caching.md) ---------------------
    # full request fingerprint (set by the front door for the
    # deterministic-batchable class); cache_mode "bypass" skips serving
    # this member from the result cache (it still fills it)
    fingerprint: str | None = None
    cache_mode: str = "use"
    # --- step-granular preemption (cluster/preemption.py) -------------------
    # checkpoint_id: parked LatentCheckpoint to resume from (set when
    # this job was preempted, or by a resume request through the front
    # door); preempt_count bounds yielding (CDT_PREEMPT_MAX);
    # resume_attempts bounds restore retries before dead-letter
    checkpoint_id: str | None = None
    preempt_count: int = 0
    resume_attempts: int = 0
    # stable arrival order within a priority class (assigned by _put;
    # a preempted job keeps its original position on requeue)
    seq: int = 0

    def expired(self, now: float) -> bool:
        return self.deadline_at is not None and now >= self.deadline_at


class PromptQueue:
    """Priority-ordered prompt queue with a single execution worker.

    Execution is serialized per controller (one mesh, one program at a
    time — the TPU analogue of one ComfyUI executor per GPU process).
    Dequeue order is strict priority class, resumes-first within a
    class, then arrival order — the scheduling half of step-granular
    preemption (``cluster/preemption.py``): preempting a low-priority
    job is only useful if the waiting high-priority job actually runs
    next, and a preempted job's parked work resumes before fresh
    arrivals of its own class.
    """

    def __init__(self, context_factory: Callable[[], dict] | None = None):
        import itertools
        import threading

        # jobs live in _pending (priority-selected at dequeue); _wake is
        # the consumer's wakeup channel — one token per _put, tokens may
        # outnumber jobs after interrupt()/expiry drains, the consumer
        # just re-checks
        self._pending: list[PromptJob] = []
        self._wake: asyncio.Queue[None] = asyncio.Queue()
        self._seq = itertools.count()
        self._context_factory = context_factory or (lambda: {})
        self._pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="graph-exec")
        self._task: Optional[asyncio.Task] = None
        self._sweep_task: Optional[asyncio.Task] = None
        self._executing: Optional[str] = None
        self.executing_job: Optional[PromptJob] = None
        self._interrupt = threading.Event()
        # cumulative seconds the consumer spent on jobs — the fused
        # path's "mesh lane busy" denominator bench.py's stages A/B
        # divides denoise-program time by (docs/stages.md)
        self.busy_seconds = 0.0
        self.history: dict[str, dict] = {}
        self._job_done_callbacks: list[Callable[[], None]] = []
        self._pending_by_priority: dict[str, int] = {}
        # step-granular preemption controller (cluster/preemption.py),
        # attached by the host controller; None = monolithic execution
        self.preemption = None
        # disaggregated stage-split serving (cluster/stages,
        # docs/stages.md), attached by the host controller; None =
        # fused group execution (CDT_STAGES=0)
        self.stages = None

    # --- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.ensure_future(self._run())
        sweep_s = constants.PREEMPT_SWEEP_S.get()
        if sweep_s > 0 and (self._sweep_task is None
                            or self._sweep_task.done()):
            self._sweep_task = asyncio.ensure_future(
                self._sweep_loop(sweep_s))

    async def stop(self) -> None:
        for task in (self._task, self._sweep_task):
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
        self._task = self._sweep_task = None
        self._pool.shutdown(wait=False, cancel_futures=True)

    def add_job_done_callback(self, cb: Callable[[], None]) -> None:
        """Called (on the event loop) after every job finishes — the
        front door uses it to flush the next coalesced group the moment
        a queue slot frees."""
        if cb not in self._job_done_callbacks:
            self._job_done_callbacks.append(cb)

    # --- producer ----------------------------------------------------------

    def enqueue(self, prompt: dict, client_id: str = "",
                trace_id: str | None = None,
                parent_span_id: str | None = None,
                tenant: str = constants.DEFAULT_TENANT,
                priority: str = constants.DEFAULT_PRIORITY,
                deadline_at: float | None = None,
                checkpoint_id: str | None = None) -> tuple[str, list]:
        """Validate + enqueue; returns (prompt_id, node_errors). Mirrors
        ``queue_prompt_payload``: validation errors reject the prompt
        before it reaches the queue (``utils/async_helpers.py:108-149``).
        ``checkpoint_id`` resumes a parked latent checkpoint
        (docs/preemption.md) — the sampler picks up mid-ladder."""
        prompt = strip_meta(prompt)
        errors = validate_prompt(prompt)
        if errors:
            return "", [e.as_dict() for e in errors]
        prompt_id = f"p_{int(time.time()*1000)}_{secrets.token_hex(3)}"
        job = PromptJob(prompt_id, prompt, client_id, trace_id,
                        parent_span_id=parent_span_id, tenant=tenant,
                        priority=priority, deadline_at=deadline_at,
                        checkpoint_id=checkpoint_id)
        self._put(job)
        return prompt_id, []

    def enqueue_batch(self, members: "list[PromptJob]",
                      sampler_node_ids: dict) -> list[str]:
        """Enqueue one batch job carrying pre-validated member prompts
        (the front door validates at submission). Returns member ids."""
        if not members:
            return []
        job = PromptJob(
            prompt_id=f"b_{int(time.time()*1000)}_{secrets.token_hex(3)}",
            prompt={}, group=list(members),
            sampler_node_ids=dict(sampler_node_ids),
            trace_id=members[0].trace_id,
            priority=min((m.priority for m in members),
                         key=_priority_rank),
        )
        self._put(job)
        return [m.prompt_id for m in members]

    def _put(self, job: PromptJob) -> None:
        if job.seq == 0:
            job.seq = next(self._seq) + 1
        self._pending.append(job)
        self._wake.put_nowait(None)
        for prio, n in _job_members(job):
            self._pending_by_priority[prio] = \
                self._pending_by_priority.get(prio, 0) + n
        if telemetry.enabled():
            _tm.PROMPT_QUEUE_DEPTH.set(self.queue_remaining)
            self._export_priority_depth()
        if self.preemption is not None:
            # a higher class arriving behind a running low-priority job
            # is THE preemption trigger (cluster/preemption.py)
            self.preemption.reevaluate()
        self.start()

    def _pop_next(self) -> Optional[PromptJob]:
        """Highest-priority pending job: class rank, resumes before
        fresh work within a class, then arrival order."""
        if not self._pending:
            return None
        job = min(self._pending, key=_dequeue_key)
        self._pending.remove(job)
        return job

    def _discard_parked(self, job: PromptJob) -> None:
        """A job dropped from the queue (interrupt, deadline expiry)
        releases its parked checkpoint — store bytes and the
        cdt_jobs_preempted gauge must not leak."""
        if self.preemption is None:
            return
        for m in (job.group or [job]):
            if getattr(m, "checkpoint_id", None):
                self.preemption.discard(m)

    def pending_best_rank(self) -> Optional[int]:
        """Best (lowest) priority rank waiting — the preemption
        controller's trigger signal. Group jobs count at their best
        member's class."""
        ranks = [min(_priority_rank(m.priority)
                     for m in (job.group or [job]))
                 for job in self._pending]
        return min(ranks) if ranks else None

    def _job_finished_accounting(self, job: PromptJob) -> None:
        for prio, n in _job_members(job):
            left = self._pending_by_priority.get(prio, 0) - n
            self._pending_by_priority[prio] = max(0, left)
        if telemetry.enabled():
            self._export_priority_depth()

    def _export_priority_depth(self) -> None:
        for prio, n in self._pending_by_priority.items():
            _tm.FD_QUEUE_DEPTH.labels(stage="queued", priority=prio).set(n)

    @property
    def queue_remaining(self) -> int:
        return len(self._pending) + (1 if self._executing else 0)

    def expire_stale(self, now: float | None = None) -> int:
        """Terminal-expire queued jobs whose deadline has passed — the
        sweep half of the freshness contract: a client's deadline is
        honored PROMPTLY, not only when a dispatch next touches the job
        (docs/preemption.md). Group jobs expire member-by-member; the
        job itself leaves the queue once every member is stale. Returns
        the number of members expired."""
        if now is None:
            now = time.monotonic()
        expired = 0
        for job in list(self._pending):
            members = job.group or [job]
            # a "preempted"/"resume_*" history row is NON-terminal — a
            # parked job waiting to resume past its deadline must sweep
            # exactly like a fresh one (its checkpoint is released)
            stale = [m for m in members if m.expired(now)
                     and self.history.get(m.prompt_id, {}).get("status")
                     not in TERMINAL_STATUSES]
            if not stale:
                continue
            if len(stale) < len(members):
                continue     # partially-stale group: execution expires
                #              the stale members individually
            self._pending.remove(job)
            for m in members:
                self.history[m.prompt_id] = {
                    "status": "expired", "duration": 0.0,
                    "error": "deadline_ms elapsed while queued",
                }
                expired += 1
                log(f"prompt {m.prompt_id} expired in queue (sweep)")
            self._discard_parked(job)
            self._job_finished_accounting(job)
            if telemetry.enabled():
                for _ in members:
                    _tm.PROMPTS_TOTAL.labels(status="expired").inc()
                _tm.PROMPT_QUEUE_DEPTH.set(self.queue_remaining)
        if expired:
            for cb in self._job_done_callbacks:
                try:
                    cb()
                except Exception:  # noqa: BLE001 — observer isolation
                    pass
        return expired

    async def _sweep_loop(self, interval_s: float) -> None:
        while True:
            await asyncio.sleep(interval_s)
            self.expire_stale()

    def interrupt(self) -> int:
        """Drop pending prompts and flag the running one (checked between
        nodes — parity with the reference's interrupt fan-out,
        ``web/workerUtils.js:73-95``). Returns number of dropped jobs
        (batch members count individually)."""
        dropped = 0
        for job in list(self._pending):
            self._pending.remove(job)
            for member in (job.group or [job]):
                self.history[member.prompt_id] = {"status": "interrupted",
                                                  "duration": 0.0}
                dropped += 1
            self._discard_parked(job)
            self._job_finished_accounting(job)
        if self._executing:
            self._interrupt.set()
        if dropped:
            # dropped jobs reached terminal history WITHOUT passing the
            # consumer loop — observers (front-door flush, coalescer
            # waiter resolution) must still see the transition, or a
            # waiter on an interrupted leader would hang forever
            for cb in self._job_done_callbacks:
                try:
                    cb()
                except Exception:  # noqa: BLE001 — observer isolation
                    pass
        return dropped

    @property
    def executing(self) -> Optional[str]:
        return self._executing

    # --- consumer ----------------------------------------------------------

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            await self._wake.get()
            job = self._pop_next()
            if job is None:
                continue     # interrupt()/sweep drained it first
            self._executing = job.prompt_id
            self.executing_job = job
            started = time.monotonic()
            self._interrupt.clear()
            statuses: list[str] = []
            try:
                if telemetry.enabled():
                    for m in (job.group or [job]):
                        waited = started - m.enqueued_at
                        _tm.QUEUE_WAIT_SECONDS.labels(
                            priority=m.priority).observe(waited)
                        # the same two timestamps as a span of the
                        # request's tree; a prompt that came without a
                        # trace id gets here the one its execution keeps
                        m.trace_id = m.trace_id or telemetry.new_trace_id()
                        telemetry.record_span(
                            "prompt.queued", waited, trace_id=m.trace_id,
                            parent_id=m.parent_span_id,
                            prompt_id=m.prompt_id)
                if self.preemption is not None:
                    # a strictly-higher class may already be waiting when
                    # a lower job starts (it was the best available)
                    self.preemption.reevaluate()
                if job.group is not None:
                    statuses = await self._run_group(loop, job, started)
                else:
                    statuses = [await self._run_solo(loop, job, started)]
            finally:
                self.busy_seconds += time.monotonic() - started
                self._executing = None
                self.executing_job = None
                if self.preemption is not None:
                    self.preemption.end(job)
                self._job_finished_accounting(job)
                if telemetry.enabled():
                    # cdt_prompts_total counts TERMINAL statuses only;
                    # a preempted/resume-retrying dispatch is the same
                    # logical prompt coming back — preemptions have
                    # their own counter (cdt_preemptions_total), and a
                    # partial segment batch must not skew the
                    # end-to-end duration histogram
                    terminal = [s for s in statuses
                                if s in TERMINAL_STATUSES]
                    for status in terminal:
                        _tm.PROMPTS_TOTAL.labels(status=status).inc()
                    if terminal and len(terminal) == len(statuses):
                        _tm.PROMPT_SECONDS.observe(
                            time.monotonic() - started)
                    _tm.PROMPT_QUEUE_DEPTH.set(self.queue_remaining)
                for cb in self._job_done_callbacks:
                    try:
                        cb()
                    except Exception:  # noqa: BLE001 — observer isolation
                        pass

    async def _run_solo(self, loop, job: PromptJob, started: float) -> str:
        if job.expired(started):
            self.history[job.prompt_id] = {
                "status": "expired", "duration": 0.0,
                "error": "deadline_ms elapsed before execution",
            }
            log(f"prompt {job.prompt_id} expired in queue")
            self._discard_parked(job)
            return "expired"
        from ..diffusion.checkpoint import (CheckpointRestoreError,
                                            PreemptedError)

        token = None
        try:
            context = dict(self._context_factory())
            context["interrupt_event"] = self._interrupt
            context["prompt_id"] = job.prompt_id
            if self.preemption is not None:
                token = self.preemption.begin(job)
                if token is not None:
                    context["preemption"] = token
            executor = GraphExecutor(context)
            # the execution span adopts the orchestration trace id and
            # parents onto the master's dispatch span (X-CDT-Trace) —
            # this is the worker-side half of a stitched job trace
            with telemetry.span("prompt.execute",
                                trace_id=job.trace_id,
                                parent_id=job.parent_span_id,
                                prompt_id=job.prompt_id):
                # run_in_executor does NOT propagate contextvars, so
                # spans opened during graph execution (the node spans,
                # pipeline_call and its launch/wait pair)
                # would start orphan traces; copying the context in
                # parents them under this execution span
                ctx = contextvars.copy_context()
                outputs = await loop.run_in_executor(
                    self._pool, ctx.run, executor.execute, job.prompt
                )
            self.history[job.prompt_id] = {
                "status": "success",
                "duration": time.monotonic() - started,
                "outputs": {
                    nid: out for nid, out in outputs.items()
                    if _is_terminal(job.prompt, nid)
                },
            }
            if job.preempt_count:
                # resumed-and-finished: the record says so (operators
                # correlate p99 outliers with preemption history)
                self.history[job.prompt_id]["preemptions"] = \
                    job.preempt_count
            if self.preemption is not None:
                if (job.checkpoint_id and token is not None
                        and token.resume is not None
                        and not token.resume_consumed):
                    # the graph never fed the checkpoint to a sampler
                    # (img2img / ControlNet path): the run is a success
                    # but it was NOT a resume — say so loudly instead
                    # of counting a phantom resume
                    log(f"prompt {job.prompt_id} IGNORED its resume "
                        f"checkpoint {job.checkpoint_id} (graph has no "
                        "preemptible sampler) — ran from scratch")
                    self.history[job.prompt_id]["resume_ignored"] = True
                    self.preemption.discard(job)
                else:
                    self.preemption.resolve_success(job)
            trace_info(job.trace_id,
                       f"prompt {job.prompt_id} done in "
                       f"{self.history[job.prompt_id]['duration']:.2f}s")
            return "success"
        except PreemptedError as e:
            # intentional departure at a segment boundary: park the
            # checkpoint, requeue at the ORIGINAL queue position (seq is
            # kept), and record a non-terminal marker — clients polling
            # history keep waiting, exactly like a still-queued job. No
            # poison count, no breaker evidence, nothing lost.
            cid = self.preemption.park(job, e.checkpoint, e.reason)
            self.history[job.prompt_id] = {
                "status": "preempted",
                "preempted_at_step": e.checkpoint.step,
                "total_steps": e.checkpoint.total_steps,
                "checkpoint_id": cid,
                "reason": e.reason,
                "duration": time.monotonic() - started,
            }
            # fresh wait clock: cdt_queue_wait_seconds on the re-dispatch
            # must measure the RE-queue wait, not fold in the segments
            # already executed since the original enqueue
            job.enqueued_at = time.monotonic()
            # clear executing_job BEFORE the requeue: _put's reevaluate
            # would otherwise see the just-parked job as still running
            # and register a spurious second preempt request against it
            self.executing_job = None
            self._put(job)
            return "preempted"
        except CheckpointRestoreError as e:
            # bounded resume retries: a checkpoint that repeatedly fails
            # restore dead-letters (forensics kept) and the job restarts
            # from scratch — it must never loop (docs/preemption.md)
            verdict = self.preemption.restore_failed(job, str(e))
            log(f"prompt {job.prompt_id} checkpoint restore failed "
                f"({e}) -> {verdict}")
            self.history[job.prompt_id] = {
                "status": "resume_retry" if verdict == "retry"
                else "resume_scratch",
                "error": str(e),
                "duration": time.monotonic() - started,
            }
            job.enqueued_at = time.monotonic()
            self.executing_job = None
            self._put(job)
            return "resume_failed"
        except InterruptedError:
            self.history[job.prompt_id] = {
                "status": "interrupted",
                "duration": time.monotonic() - started,
            }
            log(f"prompt {job.prompt_id} interrupted")
            self._discard_parked(job)
            return "interrupted"
        except Exception as e:  # noqa: BLE001 — job isolation barrier
            self.history[job.prompt_id] = {
                "status": "error", "error": str(e),
                "duration": time.monotonic() - started,
            }
            log(f"prompt {job.prompt_id} failed: {e}")
            self._discard_parked(job)
            return "error"

    async def _run_group(self, loop, job: PromptJob,
                         started: float) -> list[str]:
        """Execute a front-door batch job: expire stale members, run the
        rest through the microbatch group executor, record per-member
        history. A group never loses a member silently — every member id
        ends with a terminal history entry."""
        from .frontdoor.microbatch import execute_group

        live: list[PromptJob] = []
        statuses: list[str] = []
        for m in job.group:
            if m.expired(started):
                self.history[m.prompt_id] = {
                    "status": "expired", "duration": 0.0,
                    "error": "deadline_ms elapsed before execution",
                }
                statuses.append("expired")
            else:
                live.append(m)
        if not live:
            return statuses

        if self.stages is not None and self.stages.eligible(job):
            staged = await self._run_group_staged(loop, job, live, started)
            if staged is not None:
                return statuses + staged

        try:
            # context build INSIDE the barrier: a transient factory error
            # (mesh/registry build) must error the members, not kill the
            # consumer task and strand every future job (_run has no
            # except of its own)
            context = dict(self._context_factory())
            context["interrupt_event"] = self._interrupt
            with telemetry.span("prompt.execute_batch",
                                trace_id=job.trace_id,
                                prompt_id=job.prompt_id,
                                batch=len(live)):
                ctx = contextvars.copy_context()
                results = await loop.run_in_executor(
                    self._pool, ctx.run, execute_group,
                    live, job.sampler_node_ids, context)
        except Exception as e:  # noqa: BLE001 — group isolation barrier
            # a failure this far out (not member-isolated by the group
            # executor) marks every unfinished member errored — never lost
            log(f"batch {job.prompt_id} failed: {e}")
            results = {m.prompt_id: {"status": "error", "error": str(e)}
                       for m in live}
        duration = time.monotonic() - started
        for m in live:
            entry = results.get(m.prompt_id,
                                {"status": "interrupted"})
            status = entry.get("status", "error")
            record = {"status": status,
                      "duration": duration,
                      "batch_size": entry.get("batch_size")}
            if entry.get("cache"):
                # served from the completed-result tier (cluster/cache)
                record["cache"] = entry["cache"]
            if entry.get("error"):
                record["error"] = entry["error"]
            if status == "success":
                record["outputs"] = {
                    nid: out
                    for nid, out in (entry.get("outputs") or {}).items()
                    if _is_terminal(m.prompt, nid)
                }
            self.history[m.prompt_id] = record
            statuses.append(status)
        trace_info(job.trace_id,
                   f"batch {job.prompt_id} ({len(live)} member(s)) done "
                   f"in {duration:.2f}s")
        return statuses

    async def _run_group_staged(self, loop, job: PromptJob,
                                live: "list[PromptJob]",
                                started: float) -> "list[str] | None":
        """Route a batch job through the stage pools (cluster/stages,
        docs/stages.md): encode pool → denoise pool → decode pool. The
        consumer awaits ONLY the denoise stage — the queue slot frees
        the moment the mesh is, so the next job's denoise overlaps this
        job's decode. Per-member terminal history lands from the decode
        pool via ``_record_staged_member`` (same record shape, same
        telemetry, same job-done callbacks as the fused path). Returns
        non-terminal ``"staged"`` markers (the finally-block counts only
        TERMINAL statuses; the staged completion path owns those), or
        None if submission itself failed — the fused path then runs."""
        try:
            context = dict(self._context_factory())
            context["interrupt_event"] = self._interrupt
            denoise_done = loop.create_future()
            by_id = {m.prompt_id: m for m in live}

            def record(member, entry, last) -> None:
                self._record_staged_member(job, member, entry, last,
                                           started)

            self.stages.submit_group(
                job, live,
                {pid: job.sampler_node_ids[pid] for pid in by_id},
                context, loop, denoise_done, record)
        except Exception as e:  # noqa: BLE001 — submission barrier: the
            # fused path still exists and must serve the group instead
            log(f"stages: submit of batch {job.prompt_id} failed "
                f"({e!r}); falling back to fused execution")
            return None
        with telemetry.span("prompt.execute_batch_staged",
                            trace_id=job.trace_id,
                            prompt_id=job.prompt_id, batch=len(live)):
            await denoise_done
        trace_info(job.trace_id,
                   f"batch {job.prompt_id} ({len(live)} member(s)) "
                   f"denoise done in {time.monotonic() - started:.2f}s "
                   "(decode in flight)")
        return ["staged"] * len(live)

    def _record_staged_member(self, job: PromptJob, member: PromptJob,
                              entry: dict, last: bool,
                              started: float) -> None:
        """Terminal history for one staged member (runs on the event
        loop, marshaled from a stage worker). Mirrors the fused
        ``_run_group`` record shape exactly — pollers and the coalescer
        cannot tell the paths apart."""
        status = entry.get("status", "error")
        record = {"status": status,
                  "duration": time.monotonic() - started,
                  "batch_size": entry.get("batch_size")}
        if entry.get("decode_batch"):
            record["decode_batch"] = entry["decode_batch"]
        if entry.get("cache"):
            record["cache"] = entry["cache"]
        if entry.get("error"):
            record["error"] = entry["error"]
        if status == "success":
            record["outputs"] = {
                nid: out
                for nid, out in (entry.get("outputs") or {}).items()
                if _is_terminal(member.prompt, nid)
            }
        self.history[member.prompt_id] = record
        if telemetry.enabled():
            if status in TERMINAL_STATUSES:
                _tm.PROMPTS_TOTAL.labels(status=status).inc()
            if last:
                # end-to-end batch duration (decode included) — the
                # fused path observes the same quantity once per group
                _tm.PROMPT_SECONDS.observe(record["duration"])
        for cb in self._job_done_callbacks:
            try:
                cb()
            except Exception:  # noqa: BLE001 — observer isolation
                pass


# one terminal-status vocabulary for every history observer (pollers,
# the sweep, the coalescer via its NON_TERMINAL mirror)
TERMINAL_STATUSES = frozenset({"success", "error", "interrupted",
                               "expired"})


def _priority_rank(priority: str) -> int:
    try:
        return constants.PRIORITY_CLASSES.index(priority)
    except ValueError:
        return len(constants.PRIORITY_CLASSES)


def _dequeue_key(job: PromptJob) -> tuple:
    """Dequeue order: priority class first (group jobs at their best
    member's class), parked resumes before fresh work within a class
    (the handback front-of-queue idiom), then arrival order."""
    rank = min(_priority_rank(m.priority) for m in (job.group or [job]))
    return (rank, 0 if job.checkpoint_id else 1, job.seq)


def _job_members(job: PromptJob) -> "list[tuple[str, int]]":
    counts: dict[str, int] = {}
    for m in (job.group or [job]):
        counts[m.priority] = counts.get(m.priority, 0) + 1
    return list(counts.items())


def _is_terminal(prompt: dict, nid: str) -> bool:
    from ..graph.node import NODE_REGISTRY

    cls = NODE_REGISTRY.get(prompt.get(nid, {}).get("class_type", ""))
    if cls is None:
        return False
    consumed = {
        v[0] for node in prompt.values()
        for v in node.get("inputs", {}).values()
        if isinstance(v, (list, tuple)) and len(v) == 2
    }
    return cls.OUTPUT_NODE or nid not in consumed
