"""The ``kimi`` kind: a language model with multi-head latent attention on
every layer (a low-rank query, YaRN rotary scaling), a prefill walked in
chunks through the latent cache and routed experts computed by group,
rewriting a LONG prompt in front of a UNET image model. The cell's denoise
step is the image leg's (the configuration's file carries that leg's
``unet``/``vae`` blocks and pinned ``step_flops``), so ``step_call`` is the
UNet's; the language model's own programs are built by
``cdtbench/parity_kimi.py``. The counts the roofline shares divide by live
here, with the benchmark: ``prefill_flops`` (``kimi_prefill_mfu_pct``),
``attention_core_flops`` (``kimi_attn_core_mxu_pct``) and
``decode_bytes_per_token`` (``kimi_decode_hbm_pct``)."""

from __future__ import annotations

from cdtbench.kinds import unet
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph

# bytes a parameter, as the configuration holds them
_BYTES = {"bfloat16": 2, "float32": 4}
# the name the device trace gives the blocked causal kernel's operations
# (the jitted function around its pallas_call: ops/flash_latent.py)
ATTENTION_KERNEL = r"^latent_causal_mha"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_kimi "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


def _attention_params(config: dict) -> int:
    """One layer's attention matrices (norm weights apart)."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rq, rank = config["q_lora_rank"], config["kv_lora_rank"]
    return D * (rq + rank + rope) + rq * H * (nope + rope) \
        + rank * H * (nope + dv) + H * dv * D


def attention_core_flops(config: dict, prompt_tokens: int) -> float:
    """The blocked causal kernel's algorithmic operations in ONE prefill:
    every (query, key) pair with ``key ≤ query`` counted once —
    ``T(T+1)/2`` pairs a head a layer — times ``2·(nope + rope)`` for the
    logit and ``2·v`` for the value. A masked half of a diagonal block, a
    re-read tile or a skipped block's grid step is the kernel's cost, not
    its work."""
    pairs = prompt_tokens * (prompt_tokens + 1) / 2.0
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    return float(config["num_hidden_layers"] * config["num_attention_heads"]
                 * pairs * 2 * width)


def prefill_flops(config: dict, prompt_tokens: int,
                  held_slots: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: per layer the
    attention's projections (the latent decompressed ONCE a token: what a
    chunk re-decompresses of its prefix is the continuation's cost, not
    counted) and the causal core; the dense FFN; per expert layer the
    router, the shared expert and ``held_slots`` (one request's routed
    slots that fell on held experts, all expert layers together, as the
    program counted them) rows of one expert — never the rows a
    dense-masked form would multiply; the head on the last position."""
    T, D = prompt_tokens, config["hidden_size"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    expert = 3 * D * config["moe_intermediate_size"]
    total = layers * 2.0 * T * _attention_params(config) \
        + attention_core_flops(config, T)
    total += dense * 2.0 * T * 3 * D * config["intermediate_size"]
    total += (layers - dense) * 2.0 * T * (D * config["router_experts"]
                                           + expert)
    total += 2.0 * held_slots * expert
    total += 2.0 * config["vocab_size"] * D
    return float(total)


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts once
    (attention, the dense FFN, routers and their biases, shared experts,
    norms), the head's slice once and one row of the embedding, every
    layer's latent cache at its mean length over the request's decode, and
    ``held_share`` x top-k x expert layers routed experts. Writes are left
    out (a few KB). ``held_share`` is the DECODE steps' measured share of
    routed slots on held experts, not assumed."""
    D = config["hidden_size"]
    rq, rank, rope = (config["q_lora_rank"], config["kv_lora_rank"],
                      config["qk_rope_head_dim"])
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    attention = _attention_params(config) * w + (rq + rank) * f32
    expert = 3 * D * config["moe_intermediate_size"] * w
    moe_fixed = D * config["router_experts"] * w \
        + config["router_experts"] * f32 + expert          # + the shared
    n_moe = layers - dense
    mean_len = prompt_tokens + new_tokens / 2.0
    total = layers * (attention + 2 * D * f32) \
        + dense * 3 * D * config["intermediate_size"] * w \
        + n_moe * moe_fixed
    total += (config["vocab_size"] + 1) * D * w + D * f32
    total += layers * mean_len * (rank + rope) * w
    total += held_share * config["num_experts_per_tok"] * n_moe * expert
    return float(total)
