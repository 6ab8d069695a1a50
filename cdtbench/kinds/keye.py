"""The ``keye`` kind: a language model with grouped-query attention whose
every query reads only the ``topk`` keys a learned indexer picks for it —
the K/V rows themselves, an index-key cache beside them —, a prefill walked
in chunks through both and an expert layer that holds EVERY expert of its
router, rewriting a very long prompt in front of a UNET image model. The
cell's denoise step is the image leg's (the configuration's file carries
that leg's ``unet``/``vae`` blocks and pinned ``step_flops``), so
``step_call`` is the UNet's; the language model's own programs are built by
``cdtbench/parity_keye.py``. The counts the roofline shares divide by live
here, with the benchmark — ``prefill_flops`` (``keye_prefill_mfu_pct``),
``index_score_flops`` (``keye_index_mxu_pct``), ``selected_pair_flops``
(``keye_sparse_core_mxu_pct``), ``expert_flops`` (``keye_experts_mxu_pct``)
and ``decode_bytes_per_token`` (``keye_decode_hbm_pct``), each what the
program MUST do by the model's rule, whatever implements it — and so do the
cell's readers that are not plain data (``layer_metrics/keye_*.py`` only
name one of them). ``cdtbench/KEYE.md`` derives the counts."""

from __future__ import annotations

import dataclasses
import re

from cdtbench.kinds import glm, unet
from cdtbench.kinds.glm import SCOPES  # noqa: F401  the same three scopes
from cdtbench.kinds.jamba import hbm_peak
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import moved

KIND = "keye"
_BYTES = {"bfloat16": 2, "float32": 4}
# the names the device trace gives the kernels' operations (the jitted
# functions around their pallas_calls: ops/index_select_attention.py's
# scores and selection, ops/index_gqa_attention.py's core)
SCORE_KERNEL = r"^index_score_sums"
SELECT_KERNEL = r"^index_select_keep"
CORE_KERNEL = r"^index_masked_gqa"
EXPERTS_LAYER = "llm_experts"        # a cdt.<layer> device scope
KEYS = "cdt_llm_attn_keys_total"
SLOTS = "cdt_llm_expert_slots_total"
ROWS = "cdt_llm_expert_rows_total"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_keye "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def attention_params(config: dict) -> int:
    """One layer's attention matrices (norm weights apart): W_q, W_k, W_v,
    W_o."""
    D, d = config["hidden_size"], config["head_dim"]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    return D * (H + 2 * G) * d + H * d * D


def indexer_params(config: dict) -> int:
    """One layer's indexer matrices (its LayerNorm apart): W_Iq, W_Ik,
    W_Iw."""
    sa = config["sa_config"]
    J, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return config["hidden_size"] * (J * di + di * sa["indexer_num_kv_heads"]
                                    + J)


def expert_params(config: dict) -> int:
    """ONE expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_parameters(config: dict) -> int:
    """Every parameter of one layer: attention, the q/k norms, two layer
    norms, the indexer with its LayerNorm, the router, the held experts."""
    D = config["hidden_size"]
    return attention_params(config) + 2 * config["head_dim"] + 2 * D \
        + indexer_params(config) + 2 * config["sa_config"]["indexer_head_dim"] \
        + D * config["router_experts"] \
        + config["num_experts"] * expert_params(config)


def parameters(config: dict) -> int:
    """Every held parameter of the cut, from the configuration's sizes."""
    D = config["hidden_size"]
    return config["num_hidden_layers"] * layer_parameters(config) \
        + 2 * config["vocab_size"] * D + D


def cache_bytes_per_token(config: dict) -> int:
    """A layer's cache row: the keys and the values of every K/V head and
    the roped index key."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            + config["sa_config"]["indexer_head_dim"]) \
        * _BYTES[config["llm"]["dtype"]]


def selected_pairs(config: dict, first: int, last: int) -> float:
    """(query, key) pairs ONE head of ONE layer attends for the queries at
    positions ``first … last − 1``, by the model's rule: ``min(topk, t +
    1)`` a query."""
    import numpy as np

    t = np.arange(first, last, dtype=np.int64)
    return float(np.minimum(t + 1, config["sa_config"]["topk"]).sum())


def index_score_flops(config: dict, prompt_tokens: int) -> float:
    """The indexer's scores in ONE prefill: every (query, key) pair with
    ``key ≤ query`` once — ``T(T+1)/2`` a layer — times ``indexer_num_heads
    · indexer_head_dim · 2`` for the heads' products. The ReLU, the weights
    and the sum over heads are vector work; a masked half of a diagonal
    tile, a skipped tile's grid step or the half of the matrix unit a
    64-deep contraction leaves idle is the kernel's cost, not its work."""
    sa = config["sa_config"]
    pairs = prompt_tokens * (prompt_tokens + 1) / 2.0
    return float(config["num_hidden_layers"] * pairs * 2
                 * sa["indexer_num_heads"] * sa["indexer_head_dim"])


def selected_pair_flops(config: dict, pairs: float) -> float:
    """The matrix operations of attention over ``pairs`` selected (query,
    key) pairs a head (summed over the layers): the FEWEST any form needs,
    ``2·head_dim`` for the logit and ``2·head_dim`` for the value of every
    query head. Keys a dense-masked form multiplies and the mask drops are
    the form's cost, not its work."""
    return float(pairs * config["num_attention_heads"] * 2
                 * 2 * config["head_dim"])


def expert_flops(config: dict, slots: float) -> float:
    """``slots`` routed rows through one expert each: ``2 · 3 · D · F`` a
    row. A tile's padding is the form's cost, not its work."""
    return float(2.0 * slots * expert_params(config))


def prefill_flops(config: dict, prompt_tokens: int, pairs: float,
                  held_slots: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: per layer the
    attention's and the indexer's projections and the router, the index
    scores over the causal pairs, the SELECTED pairs ``pairs`` (a head,
    all layers together, as the program counted them), ``held_slots`` (one
    request's routed slots, all layers together, as the program counted
    them) rows of one expert; the head on the last position."""
    T, D = prompt_tokens, config["hidden_size"]
    total = config["num_hidden_layers"] * 2.0 * T * (
        attention_params(config) + indexer_params(config)
        + D * config["router_experts"])
    total += index_score_flops(config, T)
    total += selected_pair_flops(config, pairs)
    total += expert_flops(config, held_slots)
    total += 2.0 * config["vocab_size"] * D
    return float(total)


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts once
    (attention, the indexer, routers, norms), the whole head once and one
    row of the embedding; every layer's INDEX keys complete at the request's
    mean decode length and the ``min(topk, length)`` K/V rows it keeps
    (never the whole K/V cache); ``held_share`` x top-k x layers routed
    experts. Writes are left out (a few KB). ``held_share`` is the DECODE
    steps' measured share of routed slots on held experts (1: all are)."""
    D, sa = config["hidden_size"], config["sa_config"]
    di, layers = sa["indexer_head_dim"], config["num_hidden_layers"]
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    fixed = (attention_params(config) + indexer_params(config)
             + D * config["router_experts"]) * w \
        + (2 * config["head_dim"] + 2 * di + 2 * D) * f32
    mean_len = prompt_tokens + new_tokens / 2.0
    rows = min(sa["topk"], mean_len) * 2 * config["num_key_value_heads"] \
        * config["head_dim"]
    total = layers * fixed + (config["vocab_size"] + 1) * D * w + D * f32
    total += layers * (mean_len * di + rows) * w
    total += held_share * config["num_experts_per_tok"] * layers \
        * expert_params(config) * w
    return float(total)


# --- the cell's readers that are not plain data -----------------------------


def _mine(ctx: dict) -> bool:
    return ctx["cell"].config.get("kind") == KIND


def _traced_program(ctx: dict, phase: str):
    """The traced request's program of ``phase`` in a cell of this kind on
    a TPU, else None."""
    if not _mine(ctx) or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get(phase)
    return program if program and program["count"] else None


def _kernel_seconds(ctx: dict, kernel: str) -> float:
    return sum(s for op, s in ctx["trace"]["op_seconds"].items()
               if re.search(kernel, op))


def _peak_share(ctx: dict, need: float, seconds: float) -> float:
    from cdtbench.flops import peak_flops

    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def _as_glm(ctx: dict) -> dict:
    """``ctx`` with the cell marked as of the ``glm`` kind: that kind's
    readers that read nothing of a configuration's sizes but its depth (the
    clock's, the counters', the named scopes') serve this kind as they are —
    a list that may not be extended is why they test a kind at all."""
    cell = ctx["cell"]
    return {**ctx, "cell": dataclasses.replace(
        cell, config={**cell.config, "kind": glm.KIND})}


def share_pct(ctx: dict):
    """``keye_share_pct``: seconds inside the language model's two programs
    over the client's wall seconds of the window's requests."""
    return glm.share_pct(_as_glm(ctx)) if _mine(ctx) else None


def decode_ms_per_token(ctx: dict):
    """``keye_decode_ms_per_token``: host seconds inside ``llm_decode`` over
    the tokens the cell's graph asks of it."""
    return glm.decode_ms_per_token(_as_glm(ctx)) if _mine(ctx) else None


def prefill_mfu_pct(ctx: dict):
    """``keye_prefill_mfu_pct``: ``prefill_flops`` (the selected pairs and
    the routed slots as the program counted them in the window, a request)
    over the compute peak and the traced ``jit_llm_prefill``'s DEVICE time:
    the whole program's share."""
    program = _traced_program(ctx, "llm_prefill")
    if program is None or not ctx["requests"]:
        return None
    pairs = moved(ctx, KEYS, {"layers": "^sparse$", "phase": "^prefill$"})
    held = moved(ctx, SLOTS, {"phase": "^prefill$", "where": "^held$"})
    if not pairs:
        return None
    need = prefill_flops(ctx["cell"].config, request_sizes(ctx["cell"])[0],
                         pairs / ctx["requests"], held / ctx["requests"])
    return _peak_share(ctx, need, program["seconds"] / program["count"])


def decode_hbm_pct(ctx: dict):
    """``keye_decode_hbm_pct``: ``decode_bytes_per_token`` over the HBM peak
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
    """``keye_index_mxu_pct``: ``index_score_flops`` over the compute peak
    and the DEVICE seconds under the score kernel's name in the traced
    request; None where no such operation ran."""
    program = _traced_program(ctx, "llm_prefill")
    seconds = program and _kernel_seconds(ctx, SCORE_KERNEL)
    if not seconds:
        return None
    need = program["count"] * index_score_flops(
        ctx["cell"].config, request_sizes(ctx["cell"])[0])
    return _peak_share(ctx, need, seconds)


def sparse_core_mxu_pct(ctx: dict):
    """``keye_sparse_core_mxu_pct``: the SELECTED pairs' fewest operations
    (``selected_pair_flops`` of the rule's pairs for the prompt) over the
    compute peak and the DEVICE seconds under the core kernel's name in the
    traced request; None where no such operation ran."""
    program = _traced_program(ctx, "llm_prefill")
    seconds = program and _kernel_seconds(ctx, CORE_KERNEL)
    if not seconds:
        return None
    config = ctx["cell"].config
    pairs = config["num_hidden_layers"] * selected_pairs(
        config, 0, request_sizes(ctx["cell"])[0])
    need = program["count"] * selected_pair_flops(config, pairs)
    return _peak_share(ctx, need, seconds)


def selected_keys_pct(ctx: dict):
    """``keye_selected_keys_pct``: the (query, key) pairs the layers' heads
    attended in the window (the program's counter) over the causal pairs of
    the same queries: 100 the day the layer is served dense."""
    return glm.selected_keys_pct(_as_glm(ctx)) if _mine(ctx) else None


def expert_rows_per_slot(ctx: dict):
    """``keye_expert_rows_per_slot``: rows the prefill's expert form
    multiplied over the routed slots of the prefill (all held)."""
    if not _mine(ctx):
        return None
    held = moved(ctx, SLOTS, {"phase": "^prefill$", "where": "^held$"})
    rows = moved(ctx, ROWS, {"form": "^(grouped|dense)$"})
    return rows / held if held and rows else None


def _layer_report(ctx: dict):
    from cdtbench import device_layers

    return device_layers.of_run(ctx) if _mine(ctx) else None


def experts_pct(ctx: dict):
    """``keye_experts_pct``: the share of the traced language programs'
    DEVICE seconds under ``cdt.llm_experts``."""
    from cdtbench import device_layers

    return device_layers.share_pct(_layer_report(ctx), [EXPERTS_LAYER],
                                   ["llm_prefill", "llm_decode"])


def experts_mxu_pct(ctx: dict):
    """``keye_experts_mxu_pct``: ``expert_flops`` of the routed slots the
    prefill counted (a request) over the compute peak and the DEVICE
    seconds under ``cdt.llm_experts`` in the traced ``jit_llm_prefill``;
    None where the trace shows no such scope."""
    program = _traced_program(ctx, "llm_prefill")
    if program is None or not ctx["requests"]:
        return None
    answer = _layer_report(ctx) or {}
    row = answer.get("phases", {}).get("llm_prefill", {}).get(EXPERTS_LAYER)
    held = moved(ctx, SLOTS, {"phase": "^prefill$", "where": "^held$"})
    if not row or not row["seconds"] or not held:
        return None
    need = program["count"] * expert_flops(ctx["cell"].config,
                                           held / ctx["requests"])
    return _peak_share(ctx, need, row["seconds"])


def scope_pct(ctx: dict, scope: str):
    """The share of the window's busy DEVICE seconds under the plain named
    scope ``scope`` below ``cdt.llm_attn`` (the scopes are ``llm_glm``'s)."""
    return glm.scope_pct(_as_glm(ctx), scope) if _mine(ctx) else None
