"""The framework's standard metric families, declared in one place.

Instrumentation sites import these objects (no stringly-typed lookups on
the hot path) and guard every use with ``telemetry.enabled()``. Naming
follows Prometheus conventions: ``cdt_`` prefix, base-unit suffixes
(``_seconds``, ``_bytes``), counters end in ``_total``.

Label conventions (kept deliberately low-cardinality):

- ``pipeline``: compiled-program family — ``txt2img``, ``img2img``,
  ``flow_dp``, ``flow_sp``, ``video_dp``, ``video_sp``, ``video_i2v``.
- ``event`` (tiles): ``seeded`` / ``assigned`` / ``completed`` /
  ``requeued`` / ``restored`` / ``timed_out``.
- ``transport``: ``http`` / ``ws``; ``outcome``: ``ok`` / ``error`` (or
  probe-specific ``online`` / ``offline``, eviction ``evicted`` /
  ``spared``).
"""

from __future__ import annotations

from .registry import (BYTES_BUCKETS, COMPILE_BUCKETS, REGISTRY)

# --- diffusion pipelines ----------------------------------------------------

SAMPLER_STEP_SECONDS = REGISTRY.histogram(
    "cdt_sampler_step_seconds",
    "Per-step sampler wall-clock (program wall-clock / ladder steps), by "
    "pipeline. The first observation per program includes its compile — "
    "cdt_pipeline_compile_seconds carries the split.",
    ("pipeline",))

PIPELINE_COMPILE_SECONDS = REGISTRY.histogram(
    "cdt_pipeline_compile_seconds",
    "First-call wall-clock of a compiled pipeline program (trace + XLA "
    "compile + first execution), by pipeline.",
    ("pipeline",), buckets=COMPILE_BUCKETS)

PIPELINE_EXECUTE_SECONDS = REGISTRY.histogram(
    "cdt_pipeline_execute_seconds",
    "Steady-state wall-clock of a compiled pipeline program (calls after "
    "the first), by pipeline.",
    ("pipeline",))

PIPELINE_DISPATCH_SECONDS = REGISTRY.histogram(
    "cdt_pipeline_dispatch_seconds",
    "Host time inside the Python call of a bound program, until JAX "
    "hands back the not-yet-ready result (a program's first call also "
    "traces and compiles in there), by pipeline.",
    ("pipeline",))

PROGRESS_CALLBACK_SECONDS = REGISTRY.histogram(
    "cdt_progress_callback_seconds",
    "Host time of one delivery to the progress sinks: one progress "
    "callback (a denoise call's x0 preview, on a runtime thread), or, on "
    "the served lanes, the fetch of a finished segment's previews and "
    "their hand-over, one observation a segment.")

PROGRESS_EVENTS = REGISTRY.counter(
    "cdt_progress_events_total",
    "Progress events handed to the sinks (one per reporting chip), by "
    "what fed the stream: callback (jax.debug.callback inside a compiled "
    "program) or segment (outputs of a callback-free segment program, "
    "read by the host at the segment's end).",
    ("source",))

WEIGHT_PLACEMENT = REGISTRY.counter(
    "cdt_weight_placement_total",
    "Weight trees bound to a mesh program (parallel/sharding.replicate, "
    "one count a tree), by outcome: placed (some leaf was copied to the "
    "mesh's devices, under a weights.place span), reused (every leaf to "
    "place was already held for a live program of that mesh) or identity "
    "(every leaf already had the mesh's replicated sharding: a one-chip "
    "host counts nothing else).",
    ("outcome",))

WEIGHT_PLACEMENT_BYTES = REGISTRY.counter(
    "cdt_weight_placement_bytes_total",
    "Bytes of weight leaves copied by placements, summed over the devices "
    "that received a copy. Grows when a program is built, never when one "
    "is called.")

# --- collected batches (cluster/collector_bridge.py: _combine_images) ---------

COLLECTOR_BATCHES = REGISTRY.counter(
    "cdt_collector_batches_total",
    "Collects on a master, by what became of its own batch: local (no "
    "worker contributed an image: handed through as the object it came "
    "in, a device array still on its chips) or gathered (brought to the "
    "host and concatenated master-first with the workers' images).",
    ("path",))

# --- saved images (graph/nodes_builtin.py: SaveImage) -------------------------

IMAGE_SAVE_IMAGES = REGISTRY.counter(
    "cdt_image_save_images_total",
    "Images SaveImage wrote, by how their fetch, quantise, encode and write "
    "ran: pooled (a batch of several, one worker thread an image, "
    "side by side) or inline (a batch of one, on the calling thread).",
    ("mode",))

# --- the prompt rewriter (graph/nodes_builtin.py: TPUPromptRewrite) ----------

LLM_TOKENS = REGISTRY.counter(
    "cdt_llm_tokens_total",
    "Tokens a language model ran, by phase: prefill (prompt tokens of "
    "llm_prefill) and decode (tokens drawn by llm_decode).",
    ("phase",))

LLM_EXPERT_SLOTS = REGISTRY.counter(
    "cdt_llm_expert_slots_total",
    "Routed expert slots (tokens x experts per token x expert layers), by "
    "where the selected expert lives: held (on this chip, computed), "
    "absent (another chip of the expert group, left out) or zero (an "
    "identity expert of the router: no weights, computed here for every "
    "token; only a model whose router has such outputs moves it), and by "
    "the phase that routed them (prefill, decode). Counted inside the "
    "programs and fed from their outputs; a model with no expert layer "
    "moves none.",
    ("where", "phase"))

LLM_CACHE_BYTES = REGISTRY.gauge(
    "cdt_llm_cache_bytes",
    "Bytes of one rewrite request's decode cache, by the kind of layer "
    "that holds them: window (a ring of sliding_window rows a layer), "
    "full (prompt + new rows a layer), recurrent (linear-attention or "
    "state-space states and convolution tails), or a model's own kinds "
    "(latent and index: a latent cache and the index keys that choose its "
    "rows; kv and index: K/V rows and the index keys that choose them; kv "
    "and tails: K/V rows and an attention layer's convolution tails; state: "
    "retention states alone, the same bytes at every position). "
    "Set when a request's cache is made.",
    ("layers",))

LLM_PREFILL_CHUNKS = REGISTRY.counter(
    "cdt_llm_prefill_chunks_total",
    "Chunks llm_prefill walked through the cache (a model whose prompt is "
    "taken whole counts one a request).")

LLM_EXPERT_ROWS = REGISTRY.counter(
    "cdt_llm_expert_rows_total",
    "Rows the held experts' matrix products multiplied, by the form that "
    "ran: dense (every held expert x every token), grouped (the routed "
    "rows in expert order, in tiles: tiles x tile rows) or token (decode: "
    "one row a held slot). Over cdt_llm_expert_slots_total{where=held} it "
    "is what a form costs for the routed work it was needed for.",
    ("form",))

LLM_CACHE_POSITIONS = REGISTRY.gauge(
    "cdt_llm_cache_positions",
    "Positions (rows a full layer) one rewrite request's cache holds: "
    "prompt + new tokens. Set when a request's cache is made.")

LLM_STREAM_MIX = REGISTRY.counter(
    "cdt_llm_stream_mix_total",
    "Residual-stream mixes a language model ran (one Sinkhorn-normalised "
    "n x n mix a sublayer a token: 2 x layers x tokens; 0 for a model "
    "with one residual stream), by phase (prefill, decode).",
    ("phase",))

LLM_SCAN_TOKENS = REGISTRY.counter(
    "cdt_llm_scan_tokens_total",
    "Token steps a language model's selective scan walked (tokens x "
    "state-space layers: each is d_inner x d_state state updates), by "
    "phase (prefill: the chunked kernel; decode: one step a token). A "
    "model with no state-space layer never moves it.",
    ("phase",))

LLM_ATTN_KEYS = REGISTRY.counter(
    "cdt_llm_attn_keys_total",
    "(query, key) pairs ONE head attended in a language model's attention, "
    "summed over the layers of a kind: full (every key below the query: "
    "T(T+1)/2 a layer a prefill) or window (at most sliding_window keys a "
    "query: ~T x W), by phase (prefill, decode). From the config's sizes "
    "and the request's token counts; a model that does not mix the two "
    "kinds never moves it. layers=retention is no pair: a model that attends "
    "to no key counts the POSITIONS folded into its states there, tokens x "
    "layers.",
    ("layers", "phase"))

# --- attention kernel dispatch (ops/attention.py, ops/kernel_choice.py)

ATTN_KERNEL_SELECTED = REGISTRY.counter(
    "cdt_attn_kernel_selected",
    "Attention kernel-tier selections at trace time, by tier "
    "(packed/bh/xla; latent_causal, shared_kv_causal, gqa_causal, "
    "gqa_window: a chunked prefill's own kernel over a latent cache / one "
    "shared key/value head / grouped key/value heads, whole or a window's "
    "band), geometry (hH.dD.qN.kvN.dtype, dD/V where values are narrower "
    "than keys — bucketed, "
    "so cardinality is bounded by the model zoo) and resolved blocks "
    "('<block_q>/<block_k>', for packed also ':k-resident' or "
    "':k-streamed'; '' where the tier has none). Increments once per "
    "traced program per geometry; the dispatch decision is observable "
    "without a profiler.",
    ("tier", "geometry", "blocks"))

# --- tile farm --------------------------------------------------------------

TILE_EVENTS = REGISTRY.counter(
    "cdt_tile_tasks_total",
    "Tile-farm task lifecycle events.",
    ("event",))

TILE_QUEUE_DEPTH = REGISTRY.gauge(
    "cdt_tile_queue_depth",
    "Pending (unassigned) tile tasks across all live tile jobs.")

TILE_WORKER_EVICTIONS = REGISTRY.counter(
    "cdt_tile_worker_evictions_total",
    "Heartbeat-timeout verdicts on tile workers.",
    ("outcome",))   # evicted | spared | draining

# --- cluster dispatch / probing --------------------------------------------

DISPATCH_SECONDS = REGISTRY.histogram(
    "cdt_dispatch_seconds",
    "Prompt dispatch round-trip latency to a worker host.",
    ("transport", "outcome"))

DISPATCH_PAYLOAD_BYTES = REGISTRY.histogram(
    "cdt_dispatch_payload_bytes",
    "Serialized prompt payload size per dispatch.",
    ("transport",), buckets=BYTES_BUCKETS)

WORKER_PROBES = REGISTRY.counter(
    "cdt_worker_probe_total",
    "Worker health-probe outcomes (orchestration fan-out).",
    ("outcome",))   # online | offline | quarantined | draining

MEDIA_SYNC_FILES = REGISTRY.counter(
    "cdt_media_sync_files_total",
    "Per-file media sync outcomes (master -> remote host).",
    ("outcome",))   # uploaded | skipped | missing | failed

MEDIA_SYNC_BYTES = REGISTRY.counter(
    "cdt_media_sync_bytes_total",
    "Bytes uploaded by media sync.")

# --- resilience (cluster/resilience.py + cluster/faults.py) -----------------

BREAKER_STATE = REGISTRY.gauge(
    "cdt_worker_breaker_state",
    "Per-worker circuit breaker state (0=closed, 1=half-open, 2=open).",
    ("worker",))

BREAKER_TRANSITIONS = REGISTRY.counter(
    "cdt_worker_breaker_transitions_total",
    "Breaker state transitions by destination state.",
    ("to",))   # closed | half_open | open

RETRY_ATTEMPTS = REGISTRY.counter(
    "cdt_retry_attempts_total",
    "Retries performed by the unified RetryPolicy, by operation.",
    ("op",))   # dispatch | request_work | submit | collect | media | ...

FAULTS_INJECTED = REGISTRY.counter(
    "cdt_faults_injected_total",
    "Faults injected by the deterministic chaos harness (CDT_FAULTS).",
    ("op", "kind"))

# --- cold start: compile cache / warmup / residency -------------------------
# (utils/compile_cache.py, diffusion/warmup.py, cluster/residency.py)

COMPILE_CACHE_ENABLED = REGISTRY.gauge(
    "cdt_compile_cache_enabled",
    "1 once the persistent XLA compilation cache is on (the directory is "
    "logged at enable time and reported by /distributed/system_info).")

COMPILE_CACHE_REQUESTS = REGISTRY.counter(
    "cdt_compile_cache_requests_total",
    "Programs looked up in the persistent compilation cache, by outcome: "
    "hit (loaded from disk) or miss (compiled, then written). Counted by "
    "jax itself (jax.monitoring); programs it never writes — too quick "
    "to compile, or carrying a host callback — show as neither.",
    ("outcome",))

XLA_COMPILE_SECONDS = REGISTRY.histogram(
    "cdt_xla_compile_seconds",
    "Wall-clock jax spent getting one executable, compiled or loaded "
    "from the persistent cache (jax.monitoring backend_compile_duration). "
    "Its count is the process's compile count; its sum is the compile "
    "phase a warm cache shortens.",
    buckets=COMPILE_BUCKETS)

MODEL_WEIGHT_BYTES = REGISTRY.gauge(
    "cdt_model_weight_bytes",
    "Parameter bytes of a model bundle the registry built (denoiser, "
    "both VAE halves, text tower), labelled with the dtype the denoiser "
    "is held in and the text tower serving prompts.",
    ("model", "dtype", "text_tower"))

WARMUP_PROGRAMS = REGISTRY.counter(
    "cdt_warmup_programs_total",
    "AOT warmup outcomes per catalog program.",
    ("outcome",))   # cache_hit | compiled | error | skipped

WARMUP_SECONDS = REGISTRY.histogram(
    "cdt_warmup_seconds",
    "Per-program AOT lower+compile wall-clock during warmup (cache hits "
    "land in the low buckets; fresh compiles in the high ones).",
    buckets=COMPILE_BUCKETS)

WARMUP_STATE = REGISTRY.gauge(
    "cdt_warmup_state",
    "Worker warmup state (0=cold, 1=warming, 2=ready, -1=error).")

RESIDENCY_EVICTIONS = REGISTRY.counter(
    "cdt_residency_evictions_total",
    "Model bundles evicted by the HBM residency planner.",
    ("reason",))   # budget | manual

RESIDENT_MODELS = REGISTRY.gauge(
    "cdt_resident_models",
    "Model bundles currently resident under the HBM residency planner.")

RESIDENT_BYTES = REGISTRY.gauge(
    "cdt_resident_bytes",
    "Estimated bytes of resident model bundles (planner accounting).")

# --- serving front door (cluster/frontdoor, docs/serving.md) ---------------

ADMISSION_TOTAL = REGISTRY.counter(
    "cdt_admission_total",
    "Front-door admission decisions. admitted = fast path; queued = "
    "accepted past the soft high-watermark; shed = refused with 429 + "
    "Retry-After (overload or tenant rate).",
    ("outcome", "priority"))   # admitted | queued | shed

BATCH_SIZE = REGISTRY.histogram(
    "cdt_batch_size",
    "Microbatch occupancy per executed sampler program (1 = solo "
    "pass-through). Mean > 1 means cross-user coalescing is working.",
    buckets=(1, 2, 4, 8, 16, 32, 64))

BATCH_FALLBACKS = REGISTRY.counter(
    "cdt_batch_fallbacks_total",
    "Microbatched programs that failed and fell back to per-member solo "
    "execution (admitted jobs are retried solo, never dropped).")

FD_QUEUE_DEPTH = REGISTRY.gauge(
    "cdt_fd_queue_depth",
    "Per-priority-class request depth by stage: coalescing (held in a "
    "front-door window) or queued (in the prompt queue).",
    ("stage", "priority"))

QUEUE_WAIT_SECONDS = REGISTRY.histogram(
    "cdt_queue_wait_seconds",
    "Time-in-queue per request (submission to execution start, "
    "coalescing window included), by priority class.",
    ("priority",))

# --- elastic fleet (cluster/elastic, docs/elasticity.md) --------------------

AUTOSCALE_DECISIONS = REGISTRY.counter(
    "cdt_autoscale_decisions_total",
    "Autoscaler verdicts per evaluation tick. direction=up|down|hold; "
    "reason names the dominant signal (queue_pressure, idle_fleet, "
    "cooldown, envelope_min, envelope_max, no_capacity, ...).",
    ("direction", "reason"))

WORKER_DRAIN_STATE = REGISTRY.gauge(
    "cdt_worker_drain_state",
    "Per-worker lifecycle state (0=active, 1=draining, 2=decommissioned). "
    "Intentional departure — never failure evidence for the breaker.",
    ("worker",))

FLEET_SIZE = REGISTRY.gauge(
    "cdt_fleet_size",
    "Workers known to the elastic manager, by lifecycle state.",
    ("state",))   # active | draining | decommissioned

DRAIN_HANDBACKS = REGISTRY.counter(
    "cdt_drain_handbacks_total",
    "Tile tasks handed back to the queue by a draining worker "
    "(deadline expiry or early exit) — requeued WITHOUT counting toward "
    "the poison bound.")

STEAL_ASSIGNMENTS = REGISTRY.counter(
    "cdt_steal_assignments_total",
    "Cross-job scheduler grants. kind=own_job (the job the puller named) "
    "or stolen (work lifted from another open job).",
    ("kind",))

# --- content-addressed cache (cluster/cache, docs/caching.md) ---------------

CACHE_HITS = REGISTRY.counter(
    "cdt_cache_hits_total",
    "Content-cache hits by tier (conditioning = a text-encode skipped; "
    "result = a whole sampler program skipped). Disk hits count here too "
    "— a hit is a hit wherever the bytes came from.",
    ("tier",))

CACHE_MISSES = REGISTRY.counter(
    "cdt_cache_misses_total",
    "Content-cache misses by tier (the computation ran and filled the "
    "entry).",
    ("tier",))

CACHE_BYTES = REGISTRY.gauge(
    "cdt_cache_bytes",
    "In-memory bytes held per cache tier (LRU under the "
    "CDT_CACHE_*_MAX_BYTES caps).",
    ("tier",))

CACHE_ENTRIES = REGISTRY.gauge(
    "cdt_cache_entries",
    "In-memory entries per cache tier.",
    ("tier",))

CACHE_EVICTIONS = REGISTRY.counter(
    "cdt_cache_evictions_total",
    "LRU evictions per cache tier (memory budget or persisted-tier cap).",
    ("tier",))

CACHE_CORRUPT = REGISTRY.counter(
    "cdt_cache_corrupt_total",
    "Persisted cache entries rejected at load: checksum mismatch or "
    "unreadable sidecar. Always followed by a recompute — corruption is "
    "never served.",
    ("tier",))

COALESCE_WIDTH = REGISTRY.histogram(
    "cdt_coalesce_width",
    "Requests answered per executed fingerprint (1 = no duplicates were "
    "in flight; N = one execution fanned out to N-1 waiters).",
    buckets=(1, 2, 4, 8, 16, 32, 64))

# --- fleet cache tier (cluster/cache/fleet.py, docs/caching.md) -------------

FLEET_CACHE_REMOTE = REGISTRY.counter(
    "cdt_fleet_cache_remote_total",
    "Fleet-tier remote operations by op (get = probe of the ring owner; "
    "put = async fill; handback = drain-time shard move) and outcome "
    "(hit / miss / error / skipped). Every error degrades to a local "
    "recompute — the ladder never turns a slow owner into a failed "
    "request.",
    ("op", "outcome"))

FLEET_RING_SIZE = REGISTRY.gauge(
    "cdt_fleet_ring_size",
    "Workers currently owning arcs on the fleet-cache consistent-hash "
    "ring (active members; draining workers leave before decommission).")

FLEET_NEAR_REUSE = REGISTRY.counter(
    "cdt_fleet_near_reuse_total",
    "Opt-in near-tier serves: a cache:\"near\" request resumed from a "
    "donor mid-trajectory checkpoint instead of denoising from pure "
    "noise. Never bit-identical — see docs/caching.md.")

FLEET_NEAR_STEPS_SAVED = REGISTRY.counter(
    "cdt_fleet_near_steps_saved_total",
    "Denoise steps the near tier skipped (donor checkpoint step count, "
    "summed over reuses).")

HASH_TOKENIZATION = REGISTRY.counter(
    "cdt_hash_tokenization_total",
    "Text encodes that used the deterministic hash-tokenization fallback "
    "(no BPE vocab loaded), by tower. Nonzero on a production worker "
    "means conditioning does not reflect the prompt — a boot-time log "
    "line made fleet-visible (models/clip.py).",
    ("tower",))

# --- step-granular preemption (cluster/preemption.py, docs/preemption.md) ---

PREEMPTIONS_TOTAL = REGISTRY.counter(
    "cdt_preemptions_total",
    "Jobs preempted at a denoise segment boundary, by reason "
    "(priority = a higher class was waiting; drain = the worker is "
    "leaving; manual = operator request). Intentional departure — never "
    "poison or breaker evidence.",
    ("reason",))

JOBS_PREEMPTED = REGISTRY.gauge(
    "cdt_jobs_preempted",
    "Jobs currently parked mid-denoise (checkpoint held, waiting to "
    "resume).")

CHECKPOINT_BYTES = REGISTRY.gauge(
    "cdt_checkpoint_bytes",
    "Bytes of latent checkpoints held, by tier (memory / persisted).",
    ("tier",))

RESUME_SECONDS = REGISTRY.histogram(
    "cdt_resume_seconds",
    "Restore-to-first-segment-complete wall-clock when a preempted job "
    "resumes from its checkpoint (device upload + one segment program).")

CHECKPOINT_DEAD_LETTERS = REGISTRY.counter(
    "cdt_checkpoint_dead_letters_total",
    "Checkpoints dead-lettered after exhausting the resume-retry bound "
    "(CDT_PREEMPT_RESUME_RETRIES) — the job restarts from scratch "
    "instead of looping on a checkpoint that cannot restore.")

# --- disaggregated stage-split serving (cluster/stages, docs/stages.md) -----

STAGE_QUEUE_DEPTH = REGISTRY.gauge(
    "cdt_stage_queue_depth",
    "Work items queued per serving stage pool (encode / denoise / "
    "decode). Each pool scales on ITS OWN depth — a decode backlog must "
    "never read as denoise pressure (docs/stages.md).",
    ("stage",))

STAGE_OCCUPANCY = REGISTRY.gauge(
    "cdt_stage_occupancy",
    "Fraction of a stage pool's workers currently busy (0..1). The "
    "denoise pool's value is the number the whole refactor exists to "
    "raise — the mesh should spend its time denoising, not encoding or "
    "decoding.",
    ("stage",))

STAGE_JOBS = REGISTRY.counter(
    "cdt_stage_jobs_total",
    "Work items completed per stage pool, by outcome (ok / error / "
    "redispatch — redispatch = a dead worker's items re-queued to a "
    "survivor, bounded by CDT_STAGE_MAX_REDISPATCH).",
    ("stage", "outcome"))

STAGE_STEALS = REGISTRY.counter(
    "cdt_stage_steals_total",
    "Cross-stage steals: an idle host-side stage worker served the "
    "deepest sibling stage's queue (the PR 7 most-starved-first idiom "
    "generalized across stages).",
    ("src", "dst"))

DECODE_BATCH_SIZE = REGISTRY.histogram(
    "cdt_decode_batch_size",
    "Latents decoded per executed VAE program (cross-request decode "
    "coalescing per shape bucket). Mean > 1 means the decode pool is "
    "amortizing programs across concurrent requests.",
    buckets=(1, 2, 4, 8, 16, 32, 64))

LATENT_TRANSFER_BYTES = REGISTRY.histogram(
    "cdt_latent_transfer_bytes",
    "Bytes per denoise-to-decode latent handoff (host materialization, "
    "plus the checksummed wire round trip under CDT_STAGE_WIRE=1).",
    buckets=(4096, 65536, 1 << 20, 16 << 20, 256 << 20))

LATENT_TRANSFER_SECONDS = REGISTRY.histogram(
    "cdt_latent_transfer_seconds",
    "Wall-clock per latent handoff transfer — overlapped with the "
    "denoise pool's next program (T3-style), so this shows up in "
    "decode-lane latency, not denoise occupancy.")

# --- prompt queue -----------------------------------------------------------

PROMPTS_TOTAL = REGISTRY.counter(
    "cdt_prompts_total",
    "Prompt executions by terminal status.",
    ("status",))   # success | error | interrupted | expired

PROMPT_SECONDS = REGISTRY.histogram(
    "cdt_prompt_duration_seconds",
    "End-to-end graph execution wall-clock per prompt.")

PROMPT_QUEUE_DEPTH = REGISTRY.gauge(
    "cdt_prompt_queue_depth",
    "Prompts queued or executing on this controller.")

# --- HTTP control plane -----------------------------------------------------

HTTP_REQUESTS = REGISTRY.counter(
    "cdt_http_requests_total",
    "Control-plane requests by route template and status.",
    ("method", "path", "status"))

# --- worker monitor (standalone watchdog) ----------------------------------
# NOTE: when the monitor runs as its own OS process (the production
# launch path, workers/lifecycle.py) this family lives in THAT process
# and is not scrapable; it surfaces only when monitor_and_run is embedded
# in a serving process (tests, custom supervisors).

WORKER_MONITOR_CHECKS = REGISTRY.counter(
    "cdt_worker_monitor_checks_total",
    "Watchdog verdicts (master_died / worker_exit / signal).",
    ("outcome",))

# --- a later family, added where it moves no line of the code above ---------

LLM_SELECT_BLOCKS = REGISTRY.counter(
    "cdt_llm_select_blocks_total",
    "Key blocks the queries of a language model's block-selecting attention "
    "layers read (ops/block_select_attention.py), over every (layer, "
    "key/value group, query) of a request, by kind: forced (the initial "
    "block and the local ones that end at the query's own) or chosen (by "
    "compressed-key score). From the config's sizes and the request's token "
    "counts; a request within dense_len reads every block and counts them "
    "forced; a model without such layers never moves it.",
    ("kind",))

LLM_SELECT_SLOT_TILES = REGISTRY.counter(
    "cdt_llm_select_slot_tiles_total",
    "(query tile, compressed-slot tile) pairs a block-selecting language "
    "model's prefill scoring kernel met (ops/block_select_attention.py: "
    "block_score_sums), over every (layer, key/value group) of a request, "
    "by kind: scored (a query of the tile sees a window of the slot tile "
    "whole) or skipped (none does: neither fetched nor computed, read as "
    "0). From the config's sizes, the kernel's tiles and the request's "
    "token counts; a request within dense_len scores nothing.",
    ("kind",))

LLM_SELECT_COLUMNS = REGISTRY.counter(
    "cdt_llm_select_columns_total",
    "Cache columns the grid steps of an index-selecting language model's "
    "exact selection kernel met in a prefill (ops/index_select_attention.py: "
    "index_select_keep), over every (layer, step) of a request, by kind: "
    "searched (the column tiles a step's rows can see: the order image, "
    "every counting pass and the mask's work are theirs alone) and cache "
    "(whole rows: what every step visited before the kernel read its "
    "position). By the kernel's own rule at its own tiles, from the "
    "config's sizes and the request's token counts; searched / cache is "
    "how far the causal cut engages (0.5 over a prefill of many chunks, 1 "
    "where one tile is the whole cache). Decode selects in the plain form.",
    ("kind",))

# --- the set-up ledger (telemetry/build.py): exclusive SELF seconds -----------
# A program's name comes from the code base, not from traffic, so these
# families may hold more series than MAX_SERIES: past it the overflow series
# would lose the phase with the name, and the phases must add up.

PROGRAM_BUILD_SECONDS = REGISTRY.histogram(
    "cdt_program_build_seconds",
    "Seconds JAX spent building one program, by the program's name (jit_ "
    "stripped) and phase: trace, lower (Mosaic lowering of Pallas call sites "
    "is in here), cache_key + cache_read (a persistent-cache hit: the read, "
    "decompression, deserialisation and load of the executable, and the "
    "rest of the lookup) or compile (anything else: compiled), and "
    "first_run (a labelled program's first call, under its label, net of "
    "the other phases). SELF seconds: an inner program's build inside a "
    "trace is the inner program's, so the series add up to wall time. Its "
    "count is how often.",
    ("program", "phase"), buckets=(0.01, 0.1, 1.0, 10.0, 100.0),
    max_series=2048)

PROGRAM_BUILD_UNDER_SECONDS = REGISTRY.gauge(
    "cdt_program_build_under_seconds",
    "The same build seconds as cdt_program_build_seconds, by WHO asked: "
    "under is the innermost ledger entry that was open around the build "
    "(an outer program's name while it was traced or lowered, "
    "first_run:<label>, weights.<phase>:<model>, boot.<phase>) and phase is "
    "the build's own (trace, lower, cache_key, cache_read, compile, "
    "first_run). Exclusive like the other family: summed over under, a "
    "phase reads what cdt_program_build_seconds reads for it. A build is "
    "under - from the moment it ends until an entry closes around it, so - "
    "is what nothing enclosed, the one series here that can fall, and "
    "summed from the building threads' own lists when the family is read.",
    ("under", "phase"), max_series=2048)

PROGRAM_COLD_COMPILE_SECONDS = REGISTRY.counter(
    "cdt_program_cold_compile_seconds",
    "Seconds of XLA compilation each program stands for, by program: on a "
    "persistent-cache hit what JAX says the entry saved plus the read (the "
    "compile that wrote the entry), otherwise the backend event's own "
    "seconds. Summed over programs: what this process would have compiled "
    "with an empty cache. Seconds BY PROGRAM, summed over threads: a pool's "
    "builds count each for itself here (the ledger counts the pool's wall), "
    "so side by side they add up to more than the clock.",
    ("program",), max_series=1024)

PROGRAM_CACHE = REGISTRY.counter(
    "cdt_program_cache_total",
    "Executables JAX got, by the program's name and what the persistent "
    "cache did: hit (read), miss (compiled and written: on a warm start a "
    "program of >= 1 s compiled AGAIN) or uncached (compiled and not "
    "written — under the time or size threshold — or never looked up).",
    ("program", "outcome"), max_series=1024)

WEIGHTS_SECONDS = REGISTRY.histogram(
    "cdt_weights_seconds",
    "Seconds of making a model's weights, net of program builds inside: "
    "init (a bundle's construction in the registry, host seconds: model "
    "is the preset) or place (a tree's transfer onto a mesh, "
    "parallel/sharding.replicate: model is empty, a tree has no name).",
    ("model", "phase"), buckets=COMPILE_BUCKETS)

WEIGHTS_DRAWN_LEAVES = REGISTRY.counter(
    "cdt_weights_drawn_leaves_total",
    "Random-weight leaves drawn (models/draw.py: draw_params), by the "
    "bundle whose weights.init span was open (empty outside one).",
    ("model",))

WEIGHTS_DRAW_PROGRAMS = REGISTRY.counter(
    "cdt_weights_draw_programs_total",
    "Distinct draw_leaf programs those leaves went through — one a distinct "
    "(initialiser, shape, dtype, cast) — summed over a bundle's modules. "
    "Tens for 1 700 leaves; a count near the leaves' says every leaf "
    "compiled a program of its own.",
    ("model",))

BOOT_SECONDS = REGISTRY.gauge(
    "cdt_boot_seconds",
    "Seconds of the serve process's boot, by phase: import (the first line "
    "of __main__.py to the end of cmd_serve's imports, jax among them), "
    "backend (compile cache on, multi-host init, the device census: the "
    "first touch of the chip), controller (Controller() to the HTTP "
    "server listening). Net of program builds inside; set once.",
    ("phase",))

LLM_SPARSE_STEPS = REGISTRY.counter(
    "cdt_llm_sparse_steps_total",
    "Grid steps of a block-selecting language model's table-driven sparse "
    "prefill kernel (ops/block_select_attention.py: block_select_mha), over "
    "every (layer, key/value group, query tile) of a request, by how the "
    "step's K and V rows arrive: run (its blocks are consecutive: ONE copy "
    "each of blocks-a-step x block rows), blocks (a copy a block) or "
    "skipped (past the tile's union: nothing fetched or computed). Counted "
    "on the device from each tile's prefetched table by the kernel's own "
    "two rules; a request within dense_len, and the lax form, count none.",
    ("fetch",))
