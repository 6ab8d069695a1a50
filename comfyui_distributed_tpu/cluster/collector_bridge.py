"""Cross-host collector transport.

Parity: reference ``nodes/collector.py`` both roles —

- worker: PNG-encode each image, POST canonical envelopes
  ``{job_id, worker_id, batch_idx, image, is_last[, audio]}`` to the
  master's ``/distributed/job_complete`` (``:143-178``);
- master: drain the job's asyncio queue with sliced timeouts until every
  expected worker sent ``is_last``, then combine master-first/worker-order
  (``:252-295,381-499``).

On-pod gathers never touch this path (they're all_gather inside the SPMD
program); this bridge carries results **between hosts** over DCN/WAN where
a serialized envelope is genuinely required.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Optional, Sequence

import aiohttp
import numpy as np

from ..telemetry import enabled as _tm_enabled, metrics as _tm
from ..utils import constants
from ..utils.async_helpers import run_in_loop
from ..utils.audio_payload import decode_audio, encode_audio
from ..utils.exceptions import TileCollectionError, WorkerError
from ..utils.image import decode_image_b64, encode_image_b64, to_uint8, from_uint8
from ..utils.logging import debug_log, log
from ..utils.network import get_client_session, normalize_host_url, probe_host
from .job_store import JobStore


class CollectorBridge:
    """Bound to a controller's job store + event loop; node code calls the
    sync methods from the executor thread.

    ``host_resolver`` maps a worker id to its config host dict (or None);
    when provided, the master-side drain loop probes silent workers on
    timeout and extends the deadline while they are verifiably busy
    (reference busy-probe grace, ``nodes/collector.py:414-470``)."""

    def __init__(self, store: JobStore, loop: asyncio.AbstractEventLoop,
                 host_resolver=None):
        self.store = store
        self.loop = loop
        self.host_resolver = host_resolver

    # --- worker role -------------------------------------------------------

    def send(self, job_id: str, worker_id: str, images, audio,
             master_url: str) -> None:
        run_in_loop(
            self.send_async(job_id, worker_id, images, audio, master_url),
            self.loop,
            timeout=constants.DISPATCH_TIMEOUT * 4,
        )

    async def send_async(self, job_id: str, worker_id: str, images, audio,
                         master_url: str) -> None:
        arr = to_uint8(images) if images is not None else np.zeros((0, 1, 1, 3), np.uint8)
        n = arr.shape[0]
        session = get_client_session()
        if n and await self._send_frames(session, normalize_host_url(master_url),
                                         job_id, worker_id, arr, audio):
            return
        url = normalize_host_url(master_url) + "/distributed/job_complete"
        loop = asyncio.get_running_loop()
        for i in range(n):
            image_b64 = await loop.run_in_executor(
                None, encode_image_b64, arr[i])
            envelope: dict[str, Any] = {
                "job_id": job_id,
                "worker_id": worker_id,
                "batch_idx": i,
                "image": image_b64,
                "is_last": i == n - 1,
            }
            if i == n - 1 and audio is not None:
                envelope["audio"] = await loop.run_in_executor(
                    None, encode_audio, audio)
            await self._post_with_retry(session, url, envelope)
        if n == 0:
            # audio-only contribution (e.g. DistributedEmptyImage feeding
            # the image input): the completion envelope still carries the
            # AUDIO payload — dropping it here loses the worker's clip
            envelope = {
                "job_id": job_id, "worker_id": worker_id, "batch_idx": -1,
                "image": "", "is_last": True,
            }
            if audio is not None:
                envelope["audio"] = await loop.run_in_executor(
                    None, encode_audio, audio)
            await self._post_with_retry(session, url, envelope)
        debug_log(f"collector[{job_id}] worker {worker_id} sent {n} images")

    async def _send_frames(self, session, base_url: str, job_id: str,
                           worker_id: str, arr: np.ndarray, audio) -> bool:
        """Preferred transport: ONE multipart POST of crc-checked binary
        frames (native codec) instead of per-image base64-PNG JSON — the
        reference pays PNG+base64+HTTP per image (``collector.py:152-174``).
        Returns False if the master doesn't accept frames (legacy peer);
        caller falls back to the envelope protocol."""
        from .. import native

        url = base_url + "/distributed/job_complete_frames"
        loop = asyncio.get_running_loop()
        form = aiohttp.FormData()
        meta: dict[str, Any] = {"job_id": job_id, "worker_id": worker_id,
                                "count": int(arr.shape[0])}
        if audio is not None:
            meta["audio"] = await loop.run_in_executor(
                None, encode_audio, audio)
        form.add_field("metadata", json.dumps(meta),
                       content_type="application/json")
        # pack the whole batch in ONE executor hop — zlib deflate + crc
        # per multi-MB frame must not run on the event loop
        packed = await loop.run_in_executor(
            None,
            lambda: [native.pack_frame(arr[i], level=1)
                     for i in range(arr.shape[0])])
        for i, blob in enumerate(packed):
            form.add_field(f"frame_{i}", blob,
                           filename=f"frame_{i}.cdtf",
                           content_type="application/x-cdt-frame")
        try:
            async with session.post(url, data=form,
                                    headers={"X-CDT-Client": "1"}) as resp:
                if resp.status in (404, 405):
                    return False          # legacy master: use envelopes
                if resp.status < 400:
                    debug_log(f"collector[{job_id}] worker {worker_id} sent "
                              f"{arr.shape[0]} frames")
                    return True
                # any error (transient 5xx included) falls back to the
                # envelope path, which retries with exponential backoff —
                # a fire-and-forget send must never drop a finished job's
                # results on a single failed POST
                body = await resp.text()
                log(f"frame send {resp.status} ({body[:200]}); "
                    "using envelope fallback")
                return False
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            debug_log(f"frame send failed ({e}); using envelope fallback")
            return False

    async def _post_with_retry(self, session, url: str, payload: dict) -> None:
        """SEND_MAX_RETRIES attempts through the unified RetryPolicy
        (reference ``worker_comms.py:88-104``); safe to re-send because
        the master's collector drain keys envelopes by (worker_id,
        batch_idx) and duplicate is_last flags are idempotent."""
        from .resilience import send_policy

        async def attempt() -> None:
            async with session.post(url, json=payload) as resp:
                if resp.status >= 400:
                    body = await resp.text()
                    err = WorkerError(f"{resp.status}: {body[:200]}")
                    err.retry_safe = True
                    raise err

        try:
            await send_policy().run(attempt, op="collect")
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                WorkerError) as e:
            raise WorkerError(f"send to {url} failed after retries: {e}") from e

    # --- master role -------------------------------------------------------

    def collect(self, job_id: str, local_images, local_audio,
                enabled_worker_ids: Sequence[str] = (),
                delegate_only: bool = False,
                timeout: float | None = None):
        return run_in_loop(
            self.collect_async(job_id, local_images, local_audio,
                               enabled_worker_ids, delegate_only, timeout),
            self.loop,
            timeout=None,
        )

    async def collect_async(self, job_id: str, local_images, local_audio,
                            enabled_worker_ids: Sequence[str] = (),
                            delegate_only: bool = False,
                            timeout: float | None = None):
        job = await self.store.prepare_collector_job(
            job_id, tuple(enabled_worker_ids))
        overall = timeout or constants.HEARTBEAT_TIMEOUT * 4
        deadline = time.monotonic() + overall
        per_worker: dict[str, dict[int, np.ndarray]] = {w: {} for w in job.expected_workers}
        audio_parts: dict[str, dict] = {}
        # Completion is judged on the DRAIN side (is_last envelopes actually
        # consumed), never on arrival flags — otherwise the loop could exit
        # with envelopes still queued (same discipline as the reference's
        # drain loop, ``nodes/collector.py:381-499``).
        drained_done: set[str] = set()
        grace_rounds = 0

        while not drained_done >= set(job.expected_workers):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                missing = [w for w in job.expected_workers if w not in drained_done]
                busy = await self._probe_busy(missing)
                if busy and grace_rounds < constants.COLLECT_MAX_GRACE_ROUNDS:
                    grace_rounds += 1
                    deadline = time.monotonic() + constants.COLLECT_GRACE_S
                    log(f"collector[{job_id}] workers {busy} still busy; "
                        f"extending deadline (grace {grace_rounds})")
                    continue
                log(f"collector[{job_id}] timed out waiting for {missing}")
                break
            try:
                envelope = await asyncio.wait_for(
                    job.results.get(),
                    timeout=min(constants.COLLECT_POLL_TIMEOUT, remaining),
                )
            except asyncio.TimeoutError:
                continue
            w = envelope.get("worker_id", "")
            loop = asyncio.get_running_loop()
            if envelope.get("image_arr") is not None:
                per_worker.setdefault(w, {})[int(envelope.get("batch_idx", 0))] = (
                    from_uint8(envelope["image_arr"])
                )
            elif envelope.get("image"):
                per_worker.setdefault(w, {})[int(envelope.get("batch_idx", 0))] = (
                    await loop.run_in_executor(
                        None, decode_image_b64, envelope["image"])
                )
            if envelope.get("audio"):
                audio_parts[w] = await loop.run_in_executor(
                    None, decode_audio, envelope["audio"])
            if envelope.get("is_last"):
                drained_done.add(w)

        images = self._combine_images(local_images, per_worker, job.expected_workers,
                                      delegate_only)
        audio = self._combine_audio(local_audio, audio_parts, job.expected_workers)
        await self.store.cleanup_job(job_id)
        return images, audio

    async def _probe_busy(self, missing: Sequence[str]) -> list[str]:
        """Probe silent workers' health; return those with work still
        queued/executing. A dead host (probe None) or an idle one gets no
        grace — only a verifiably busy worker extends the drain deadline."""
        if self.host_resolver is None or not missing:
            return []
        resolvable = [(w, self.host_resolver(w)) for w in missing]
        resolvable = [(w, h) for w, h in resolvable if h]
        statuses = await asyncio.gather(
            *(probe_host(h) for _, h in resolvable))
        return [
            w for (w, _), status in zip(resolvable, statuses)
            if status and int(status.get("queue_remaining", 0) or 0) > 0
        ]

    @staticmethod
    def _combine_images(local_images, per_worker, expected: Sequence[str],
                        delegate_only: bool):
        """Master first, then workers in enabled order, batch_idx order
        within each worker (``nodes/collector.py:252-295``). A delegate-only
        master contributes nothing (``:329-333``).

        When no worker contributed an image there is nothing to
        concatenate with, and ``local_images`` is handed through as the
        object it came in: a device array stays on its chips, sharded as
        the program left it, for the consumer to fetch from. Only a
        worker's images (host arrays) bring the master's batch to the
        host. ``cdt_collector_batches_total{path}`` counts which ran."""
        remote = [imgs[idx][None]
                  for imgs in (per_worker.get(w, {}) for w in expected)
                  for idx in sorted(imgs)]
        if _tm_enabled():
            _tm.COLLECTOR_BATCHES.labels(
                path="gathered" if remote else "local").inc()
        if not remote:
            return local_images
        batches: list[np.ndarray] = []
        if local_images is not None and not delegate_only:
            local = np.asarray(local_images, dtype=np.float32)
            if local.size:
                batches.append(local)
        batches.extend(remote)
        hw = batches[0].shape[1:3]
        kept = [b for b in batches if b.shape[1:3] == hw]
        if len(kept) != len(batches):
            log(f"collector: dropping {len(batches)-len(kept)} mismatched-size results")
        return np.concatenate(kept, axis=0)

    @staticmethod
    def _combine_audio(local_audio, audio_parts, expected: Sequence[str]):
        """Concatenate waveforms along samples (``:180-233``)."""
        parts = []
        if local_audio is not None:
            parts.append(local_audio)
        parts.extend(audio_parts[w] for w in expected if w in audio_parts)
        if not parts:
            return None
        sr = parts[0]["sample_rate"]
        wfs = [np.asarray(p["waveform"]) for p in parts]
        ch = min(w.shape[1] for w in wfs)
        wfs = [w[:, :ch, :] for w in wfs]
        return {"waveform": np.concatenate(wfs, axis=-1), "sample_rate": sr}
