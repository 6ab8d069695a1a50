"""The ``longcat`` kind: a language model of DOUBLE layers (two latent
attentions and two dense FFNs in series, one routed-expert branch beside
them) whose softmax router has identity experts among its outputs,
rewriting a long prompt in front of a UNET image model. The cell's denoise
step is the image leg's (the configuration's file carries that leg's
``unet``/``vae`` blocks and pinned ``step_flops``), so ``step_call`` is the
UNet's; the language model's own programs are built by
``cdtbench/parity_longcat.py``. The counts the roofline shares divide by
live here, with the benchmark — ``prefill_flops``
(``longcat_prefill_mfu_pct``), ``attention_core_flops``
(``longcat_attn_core_mxu_pct``) and ``decode_bytes_per_token``
(``longcat_decode_hbm_pct``), each what the program MUST do, whatever
implements it — and so do the cell's four readers that are not plain data
(``layer_metrics/longcat_*.py`` only name one of them)."""

from __future__ import annotations

import re

from cdtbench.kinds import unet
from cdtbench.kinds.jamba import hbm_peak
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import moved

KIND = "longcat"
# bytes a parameter, as the configuration holds them
_BYTES = {"bfloat16": 2, "float32": 4}
# the name the device trace gives the blocked causal kernel's operations
# (the jitted function around its pallas_call: ops/flash_latent.py) — the
# kimi kind's kernel, at the same head sizes
ATTENTION_KERNEL = r"^latent_causal_mha"
SLOTS = "cdt_llm_expert_slots_total"
SECONDS = "cdt_pipeline_execute_seconds"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_longcat "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def _attention_params(config: dict) -> int:
    """One attention sublayer's matrices (norm weights apart)."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rq, rank = config["q_lora_rank"], config["kv_lora_rank"]
    return D * (rq + rank + rope) + rq * H * (nope + rope) \
        + rank * H * (nope + dv) + H * dv * D


def _router_outputs(config: dict) -> int:
    return config["router_experts"] + config["zero_expert_num"]


def attention_core_flops(config: dict, prompt_tokens: int) -> float:
    """The blocked causal kernel's algorithmic operations in ONE prefill:
    every (query, key) pair with ``key ≤ query`` counted once —
    ``T(T+1)/2`` pairs a head a SUBLAYER, two sublayers a double layer —
    times ``2·(nope + rope)`` for the logit and ``2·v`` for the value. A
    masked half of a diagonal block, a re-read tile or a skipped block's
    grid step is the kernel's cost, not its work."""
    pairs = prompt_tokens * (prompt_tokens + 1) / 2.0
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    return float(2 * config["num_layers"] * config["num_attention_heads"]
                 * pairs * 2 * width)


def prefill_flops(config: dict, prompt_tokens: int,
                  held_slots: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: per double layer
    two attentions' projections (the latent decompressed ONCE a token:
    what a chunk re-decompresses of its prefix is the continuation's cost,
    not counted) and causal cores, two dense FFNs, the router over all its
    outputs, and ``held_slots`` (one request's routed slots that fell on
    held experts, all layers together, as the program counted them) rows
    of one expert — never the padded rows of a grouped tile; a slot on an
    identity expert is a row times a scalar, counted as nothing; the head
    at ONE position."""
    T, D = prompt_tokens, config["hidden_size"]
    layers = config["num_layers"]
    expert = 3 * D * config["expert_ffn_hidden_size"]
    total = layers * 2 * 2.0 * T * (_attention_params(config)
                                    + 3 * D * config["ffn_hidden_size"])
    total += attention_core_flops(config, T)
    total += layers * 2.0 * T * D * _router_outputs(config)
    total += 2.0 * held_slots * expert
    total += 2.0 * config["vocab_size"] * D
    return float(total)


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts once
    (per double layer two attentions, two dense FFNs, four stream norms,
    the router and its bias), the head's slice once and one row of the
    embedding, BOTH latent caches of every layer at their mean length over
    the request's decode, and ``held_share`` x top-k x layers routed
    experts (an identity expert reads nothing). Writes are left out (a few
    KB). ``held_share`` is the DECODE steps' measured share of ALL routed
    slots (held, absent and zero) on held experts, not assumed."""
    D = config["hidden_size"]
    rq, rank, rope = (config["q_lora_rank"], config["kv_lora_rank"],
                      config["qk_rope_head_dim"])
    layers, outputs = config["num_layers"], _router_outputs(config)
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    sublayer = _attention_params(config) * w + (rq + rank) * f32 \
        + 2 * D * f32 + 3 * D * config["ffn_hidden_size"] * w
    expert = 3 * D * config["expert_ffn_hidden_size"] * w
    mean_len = prompt_tokens + new_tokens / 2.0
    total = layers * (2 * sublayer + D * outputs * w + outputs * f32)
    total += (config["vocab_size"] + 1) * D * w + D * f32
    total += 2 * layers * mean_len * (rank + rope) * w
    total += held_share * config["moe_topk"] * layers * expert
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


def share_pct(ctx: dict):
    """``longcat_share_pct``: seconds inside the language model's two
    programs over the client's wall seconds of the window's requests."""
    if ctx["cell"].config.get("kind") != KIND:
        return None
    done = [r for r in ctx["records"] if r["status"] == "success"]
    inside = moved(ctx, SECONDS, {"pipeline": "^llm_(prefill|decode)$"},
                   "sum")
    if not done or inside == 0.0:
        return None
    return 100.0 * inside / sum(r["seconds"] for r in done)


def prefill_mfu_pct(ctx: dict):
    """``longcat_prefill_mfu_pct``: ``prefill_flops`` (held rows from the
    window's counter, per request) over the compute peak and the traced
    ``jit_llm_prefill``'s DEVICE time: the whole program's share."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    if program is None or not ctx["requests"]:
        return None
    held = moved(ctx, SLOTS, {"phase": "^prefill$", "where": "^held$"})
    need = prefill_flops(ctx["cell"].config, request_sizes(ctx["cell"])[0],
                         held / ctx["requests"])
    seconds = program["seconds"] / program["count"]
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def decode_hbm_pct(ctx: dict):
    """``longcat_decode_hbm_pct``: ``decode_bytes_per_token`` (the held
    share counted in the window's decode steps) over the HBM peak and the
    traced ``jit_llm_decode``'s DEVICE time a token."""
    program = _traced_program(ctx, "llm_decode")
    slots = moved(ctx, SLOTS, {"phase": "^decode$"})
    if program is None or not slots:
        return None
    held_share = moved(ctx, SLOTS, {"phase": "^decode$",
                                    "where": "^held$"}) / slots
    prompt_tokens, new_tokens = request_sizes(ctx["cell"])
    token_s = program["seconds"] / program["count"] / new_tokens
    need = decode_bytes_per_token(ctx["cell"].config, held_share,
                                  prompt_tokens, new_tokens)
    return 100.0 * need / hbm_peak(ctx["device"]["kind"]) / token_s


def attn_core_mxu_pct(ctx: dict):
    """``longcat_attn_core_mxu_pct``: ``attention_core_flops`` over the
    compute peak and the DEVICE seconds under the kernel's name in the
    traced request; None where no such operation ran."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    if program is None:
        return None
    seconds = sum(s for op, s in ctx["trace"]["op_seconds"].items()
                  if re.search(ATTENTION_KERNEL, op))
    if not seconds:
        return None
    need = program["count"] * attention_core_flops(
        ctx["cell"].config, request_sizes(ctx["cell"])[0])
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds
