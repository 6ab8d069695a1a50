"""The ``glm`` kind: a language model with multi-head latent attention whose
every query reads only the ``index_topk`` keys a learned indexer picks for
it (an index-key cache beside the latent cache), a prefill walked in chunks
through both and routed experts computed by group, rewriting a very long
prompt in front of a UNET image model. The cell's denoise step is the image
leg's (the configuration's file carries that leg's ``unet``/``vae`` blocks
and pinned ``step_flops``), so ``step_call`` is the UNet's; the language
model's own programs are built by ``cdtbench/parity_glm.py``. The counts
the roofline shares divide by live here, with the benchmark —
``prefill_flops`` (``glm_prefill_mfu_pct``), ``index_score_flops``
(``glm_index_mxu_pct``), ``selected_pair_flops``
(``glm_sparse_core_mxu_pct``) and ``decode_bytes_per_token``
(``glm_decode_hbm_pct``), each what the program MUST do by the model's
rule, whatever implements it — and so do the cell's readers that are not
plain data (``layer_metrics/glm_*.py`` only name one of them). The prefill's
counts are taken over the tokens the program says it ran in the TRACED
request (``traced_moved``, ``prefilled_from``): a request whose prefix was
kept from an earlier ask is counted from where its ``llm_prefill`` started,
never over the whole prompt, and never as the mean of a window that also
held a first ask. ``cdtbench/GLM.md`` derives the counts."""

from __future__ import annotations

import re

from cdtbench.kinds import unet
from cdtbench.kinds.jamba import hbm_peak
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import moved

KIND = "glm"
_BYTES = {"bfloat16": 2, "float32": 4}
# the names the device trace gives the three kernels' operations (the jitted
# functions around their pallas_calls: ops/index_select_attention.py)
SCORE_KERNEL = r"^index_score_sums"
SELECT_KERNEL = r"^index_select_keep"
CORE_KERNEL = r"^index_masked_mha"
# plain named scopes below cdt.llm_attn (models/llm_glm.py): a component of
# an operation's name stack (tf_op)
SCOPES = ("llm_index", "llm_select", "llm_sparse_attn")
KEYS = "cdt_llm_attn_keys_total"
SLOTS = "cdt_llm_expert_slots_total"
TOKENS = "cdt_llm_tokens_total"
SECONDS = "cdt_pipeline_execute_seconds"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_glm "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def attention_params(config: dict) -> int:
    """One layer's attention matrices (norm weights apart)."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rq, rank = config["q_lora_rank"], config["kv_lora_rank"]
    return D * (rq + rank + rope) + rq * H * (nope + rope) \
        + rank * H * (nope + dv) + H * dv * D


def indexer_params(config: dict) -> int:
    """One layer's indexer matrices (its LayerNorm apart): W_Iq, W_Ik,
    W_Iw."""
    J, di = config["index_n_heads"], config["index_head_dim"]
    return config["q_lora_rank"] * J * di + config["hidden_size"] * (di + J)


def parameters(config: dict) -> int:
    """Every held parameter of the cut, from the configuration's sizes."""
    D = config["hidden_size"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    expert = 3 * D * config["moe_intermediate_size"]
    per_layer = attention_params(config) + indexer_params(config) \
        + 2 * config["index_head_dim"] + 2 * D + config["q_lora_rank"] \
        + config["kv_lora_rank"]
    moe = D * config["router_experts"] + config["router_experts"] \
        + expert * (config["n_shared_experts"] + config["n_routed_experts"])
    return layers * per_layer + dense * 3 * D * config["intermediate_size"] \
        + (layers - dense) * moe + 2 * config["vocab_size"] * D + D


def cache_bytes_per_token(config: dict) -> int:
    """A layer's cache row: the latent, the roped shared key and the roped
    index key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]
            + config["index_head_dim"]) * _BYTES[config["llm"]["dtype"]]


def selected_pairs(config: dict, first: int, last: int) -> float:
    """(query, key) pairs ONE head of ONE layer attends for the queries at
    positions ``first … last − 1``, by the model's rule: ``min(index_topk,
    t + 1)`` a query."""
    import numpy as np

    t = np.arange(first, last, dtype=np.int64)
    return float(np.minimum(t + 1, config["index_topk"]).sum())


def causal_pairs(first: int, last: int) -> float:
    """(query, key) pairs with ``key ≤ query`` for the queries at positions
    ``first … last − 1``: ``(last(last+1) − first(first+1))/2``."""
    return (last * (last + 1) - first * (first + 1)) / 2.0


def index_score_flops(config: dict, prompt_tokens: int,
                      first: int = 0) -> float:
    """The indexer's scores in ONE prefill that runs the queries at
    positions ``first … T − 1`` (``first`` > 0: a kept prefix, whose keys
    are still scored against): every (query, key) pair with ``key ≤ query``
    once — ``T(T+1)/2`` a layer from 0 — times ``index_n_heads ·
    index_head_dim · 2`` for the heads' products. The ReLU, the weights and
    the sum over heads are vector work; a masked half of a diagonal tile or
    a skipped tile's grid step is the kernel's cost, not its work."""
    pairs = causal_pairs(first, prompt_tokens)
    return float(config["num_hidden_layers"] * pairs * 2
                 * config["index_n_heads"] * config["index_head_dim"])


def selected_pair_flops(config: dict, pairs: float,
                        absorbed: bool = False) -> float:
    """The matrix operations of attention over ``pairs`` selected (query,
    key) pairs a head (summed over the layers): the FEWEST any form needs,
    ``2·(nope + rope)`` for the logit and ``2·v`` for the value of every
    head — or, ``absorbed``, what the form that never decompresses does for
    them: ``2·(rank + rope)`` and ``2·rank``. Keys a dense-masked form
    multiplies and the mask drops are the form's cost, not its work."""
    H = config["num_attention_heads"]
    rope = config["qk_rope_head_dim"]
    if absorbed:
        width = 2 * config["kv_lora_rank"] + rope
    else:
        width = config["qk_nope_head_dim"] + rope + config["v_head_dim"]
    return float(pairs * H * 2 * width)


def prefill_flops(config: dict, prompt_tokens: int, pairs: float,
                  held_slots: float, first: int = 0) -> float:
    """The algorithmic operations of ONE ``llm_prefill`` that runs the
    tokens at positions ``first … T − 1`` (everything linear in the tokens
    over ``T − first`` of them, the index scores over the causal pairs of
    those queries; ``pairs`` and ``held_slots`` are the program's own
    counts of what it ran): per layer the
    attention's and the indexer's projections (the latent decompressed ONCE
    a token), the index scores over the causal pairs, the SELECTED pairs
    ``pairs`` (a head, all layers together, as the program counted them) in
    the absorbed form; the dense FFN; per expert layer the router, the
    shared expert and ``held_slots`` (one request's routed slots that fell
    on held experts, all expert layers together, as the program counted
    them) rows of one expert; the head on the last position."""
    ran, D = prompt_tokens - first, config["hidden_size"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    expert = 3 * D * config["moe_intermediate_size"]
    total = layers * 2.0 * ran * (attention_params(config)
                                  + indexer_params(config))
    total += index_score_flops(config, prompt_tokens, first)
    total += selected_pair_flops(config, pairs, absorbed=True)
    total += dense * 2.0 * ran * 3 * D * config["intermediate_size"]
    total += (layers - dense) * 2.0 * ran * (D * config["router_experts"]
                                             + expert)
    total += 2.0 * held_slots * expert
    total += 2.0 * config["vocab_size"] * D
    return float(total)


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts once
    (attention, the indexer, the dense FFN, routers and their biases,
    shared experts, norms), the head's slice once and one row of the
    embedding; every layer's INDEX keys complete at the request's mean
    decode length and the ``min(index_topk, length)`` latent rows it keeps
    (never the whole latent cache); ``held_share`` x top-k x expert layers
    routed experts. Writes are left out (a few KB). ``held_share`` is the
    DECODE steps' measured share of routed slots on held experts."""
    D = config["hidden_size"]
    rq, rank, rope = (config["q_lora_rank"], config["kv_lora_rank"],
                      config["qk_rope_head_dim"])
    di = config["index_head_dim"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    attention = (attention_params(config) + indexer_params(config)) * w \
        + (rq + rank + 2 * di) * f32
    expert = 3 * D * config["moe_intermediate_size"] * w
    moe_fixed = D * config["router_experts"] * w \
        + config["router_experts"] * f32 + expert          # + the shared
    n_moe = layers - dense
    mean_len = prompt_tokens + new_tokens / 2.0
    total = layers * (attention + 2 * D * f32) \
        + dense * 3 * D * config["intermediate_size"] * w \
        + n_moe * moe_fixed
    total += (config["vocab_size"] + 1) * D * w + D * f32
    total += layers * (mean_len * di
                       + min(config["index_topk"], mean_len)
                       * (rank + rope)) * w
    total += held_share * config["num_experts_per_tok"] * n_moe * expert
    return float(total)


# --- the cell's readers that are not plain data -----------------------------


def _traced_program(ctx: dict, phase: str):
    """The traced request's program of ``phase`` in a cell of this kind on
    a TPU, else None."""
    if ctx["cell"].config.get("kind") != KIND or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get(phase)
    return program if program and program["count"] else None


def traced_moved(ctx: dict, series: str, match: dict):
    """How far a counter moved A REQUEST over the traced request(s):
    between the two snapshots ``run.py`` takes as the profiler starts and
    as it stops (``ctx["traced"]``) — the counts that belong to the device
    time the trace gives, whatever else the window held: a first ask beside
    repeats of a kept brief is read as what it was, never as the window's
    mean. None where the profile never closed. A ctx made by hand without
    the key (``tests/test_llm_glm.py``) is read over its window, whose
    requests are alike."""
    from cdtbench.readers import total

    span = ctx["traced"] if "traced" in ctx else {
        k: ctx[k] for k in ("opened", "closed", "requests")}
    if not span or not span["requests"]:
        return None
    cell = ctx["cell"]
    return (total(span["closed"], series, match, "value", cell)
            - total(span["opened"], series, match, "value", cell)) \
        / span["requests"]


def prefilled_from(ctx: dict):
    """The position the traced request's ``llm_prefill`` started at: the
    prompt's length less the tokens the program says it prefilled
    (``traced_moved`` of ``cdt_llm_tokens_total{phase=prefill}``). None —
    and every count over the range with it, said in the run's log — where
    that is not a whole number of tokens within the prompt: never a guess.
    Only a program that has no such series at all (every program before
    the series; the hand-made snapshots of ``tests/test_llm_glm.py``) is
    counted over its whole prompt, which is also said."""
    from cdtbench.server import say, series

    prompt_tokens = request_sizes(ctx["cell"])[0]
    ran = traced_moved(ctx, TOKENS, {"phase": "^prefill$"})
    if ran is None:
        return None
    if not series((ctx.get("traced") or ctx)["closed"], TOKENS):
        say(f"no {TOKENS} series: the whole prompt is counted")
        return 0
    if ran != int(ran) or not 0 < ran <= prompt_tokens:
        say(f"{TOKENS}{{phase=prefill}} moved {ran:g} a traced request of "
            f"{prompt_tokens} prompt tokens: no count of what llm_prefill "
            "ran, and none of its shares of a peak")
        return None
    return prompt_tokens - int(ran)


def _kernel_seconds(ctx: dict, kernel: str) -> float:
    return sum(s for op, s in ctx["trace"]["op_seconds"].items()
               if re.search(kernel, op))


def share_pct(ctx: dict):
    """``glm_share_pct``: seconds inside the language model's two programs
    over the client's wall seconds of the window's requests."""
    if ctx["cell"].config.get("kind") != KIND:
        return None
    done = [r for r in ctx["records"] if r["status"] == "success"]
    inside = moved(ctx, SECONDS, {"pipeline": "^llm_(prefill|decode)$"},
                   "sum")
    if not done or inside == 0.0:
        return None
    return 100.0 * inside / sum(r["seconds"] for r in done)


def decode_ms_per_token(ctx: dict):
    """``glm_decode_ms_per_token``: host seconds inside ``llm_decode`` over
    the tokens the cell's graph asks of it."""
    if ctx["cell"].config.get("kind") != KIND or not ctx["requests"]:
        return None
    inside = moved(ctx, SECONDS, {"pipeline": "^llm_decode$"}, "sum")
    if inside == 0.0:
        return None
    return 1000.0 * inside / ctx["requests"] / request_sizes(ctx["cell"])[1]


def prefill_mfu_pct(ctx: dict):
    """``glm_prefill_mfu_pct``: ``prefill_flops`` (the range, the selected
    pairs and the held slots as the program counted them for the TRACED
    request) over the compute peak and the traced ``jit_llm_prefill``'s
    DEVICE time: the whole program's share."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    if program is None:
        return None
    pairs = traced_moved(ctx, KEYS, {"layers": "^sparse$",
                                     "phase": "^prefill$"})
    held = traced_moved(ctx, SLOTS, {"phase": "^prefill$",
                                     "where": "^held$"})
    first = pairs and prefilled_from(ctx)
    if not pairs or first is None:
        return None
    need = prefill_flops(ctx["cell"].config, request_sizes(ctx["cell"])[0],
                         pairs, held, first)
    seconds = program["seconds"] / program["count"]
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def decode_hbm_pct(ctx: dict):
    """``glm_decode_hbm_pct``: ``decode_bytes_per_token`` over the HBM peak
    and the traced ``jit_llm_decode``'s DEVICE time a token."""
    program = _traced_program(ctx, "llm_decode")
    slots = program and moved(ctx, SLOTS, {"phase": "^decode$"})
    if not slots:
        return None
    held_share = moved(ctx, SLOTS, {"phase": "^decode$",
                                    "where": "^held$"}) / slots
    prompt_tokens, new_tokens = request_sizes(ctx["cell"])
    token_s = program["seconds"] / program["count"] / new_tokens
    need = decode_bytes_per_token(ctx["cell"].config, held_share,
                                  prompt_tokens, new_tokens)
    return 100.0 * need / hbm_peak(ctx["device"]["kind"]) / token_s


def index_mxu_pct(ctx: dict):
    """``glm_index_mxu_pct``: ``index_score_flops`` over the compute peak
    and the DEVICE seconds under the score kernel's name in the traced
    request; None where no such operation ran."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    seconds = program and _kernel_seconds(ctx, SCORE_KERNEL)
    first = seconds and prefilled_from(ctx)
    if not seconds or first is None:
        return None
    need = program["count"] * index_score_flops(
        ctx["cell"].config, request_sizes(ctx["cell"])[0], first)
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def sparse_core_mxu_pct(ctx: dict):
    """``glm_sparse_core_mxu_pct``: the SELECTED pairs' fewest operations
    (``selected_pair_flops`` of the rule's pairs for the prompt) over the
    compute peak and the DEVICE seconds under the core kernel's name in the
    traced request; None where no such operation ran."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    seconds = program and _kernel_seconds(ctx, CORE_KERNEL)
    first = seconds and prefilled_from(ctx)
    if not seconds or first is None:
        return None
    config = ctx["cell"].config
    pairs = config["num_hidden_layers"] * selected_pairs(
        config, first, request_sizes(ctx["cell"])[0])
    need = program["count"] * selected_pair_flops(config, pairs)
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def selected_keys_pct(ctx: dict):
    """``glm_selected_keys_pct``: the (query, key) pairs the layers' heads
    attended in the traced request (the program's counter) over the causal
    pairs of the same queries: 100 the day the layer is served dense."""
    cell = ctx["cell"]
    if cell.config.get("kind") != KIND:
        return None
    seen = traced_moved(ctx, KEYS, {"layers": "^sparse$"})
    first = seen and prefilled_from(ctx)
    if not seen or first is None:
        return None
    causal = cell.config["num_hidden_layers"] * causal_pairs(
        first, sum(request_sizes(cell)))
    return 100.0 * seen / causal


_scope_seconds: dict = {}


def scope_seconds(ctx: dict):
    """DEVICE seconds (self times, mean over the chips) of the traced
    window's operations by the plain named scope of :data:`SCOPES` in their
    name stack: read from the trace's event metadata as
    ``cdtbench/device_layers.py`` reads the ``cdt.<layer>`` scopes, once a
    trace (``kinds/sala.py: scope_seconds`` with this kind's scopes). None
    without a trace or where no operation carries such a scope (the parent;
    any cell of another kind)."""
    if ctx["cell"].config.get("kind") != KIND or ctx.get("trace") is None:
        return None
    from cdtbench import device_layers as dl

    out_dir = dl.ROOT / "chiprun_out" / "cdtbench" / ctx["cell"].name
    xplane = dl.find_xplane(out_dir / "profile")
    if xplane is None:
        return None
    key = str(dl._key(xplane))
    if key not in _scope_seconds:
        found = {scope: 0.0 for scope in SCOPES}
        part = re.compile(r"/(" + "|".join(SCOPES) + r")(?:/|$)")
        planes = [p for p in dl.read_space(xplane)
                  if p["lines"].get(dl.OPS_LINE)]
        for plane in planes:
            ops = {k: dl.describe(meta)
                   for k, meta in plane["metadata"].items()}
            for op, own in dl.self_times(plane["lines"][dl.OPS_LINE]):
                hit = part.search(ops[op]["tf_op"])
                if hit and not ops[op]["control_flow"]:
                    found[hit.group(1)] += own * (dl.PS / 1e-9) / len(planes)
        _scope_seconds[key] = found if any(found.values()) else None
    return _scope_seconds[key]


def scope_pct(ctx: dict, scope: str):
    """The share of the window's busy DEVICE seconds under ``scope``."""
    found = scope_seconds(ctx)
    if not found or not found.get(scope) or not ctx["trace"].get("busy_s"):
        return None
    return 100.0 * found[scope] / ctx["trace"]["busy_s"]
