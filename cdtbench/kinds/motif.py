"""The ``motif`` kind: a language model with grouped differential latent
attention over window and full layers, a multi-stream residual and PolyNorm
experts, rewriting the prompt in front of a UNET image model. The cell's
denoise step is the image leg's (the configuration's file carries that
leg's ``unet``/``vae`` blocks and pinned ``step_flops``), so ``step_call``
is the UNet's; the language model's own programs are built by
``cdtbench/parity_motif.py``. ``decode_bytes_per_token`` is the numerator
of ``motif_decode_hbm_pct``."""

from __future__ import annotations

from cdtbench.kinds import unet
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph

# bytes a parameter, as the configuration holds them
_BYTES = {"bfloat16": 2, "float32": 4}


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_motif "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


def is_full(config: dict, i: int) -> bool:
    return (i + 1) % config["sliding_window_period"] == 0


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts once
    (attention, both stream mixers of every layer, the dense MLP, routers,
    shared experts, norms), the head's slice once and one row of the
    embedding, each window layer's ring, each full layer's cache at its
    mean length over the request's decode, and ``held_share`` x top-k x
    expert layers routed experts. Writes are left out (a few KB).
    ``held_share`` is the DECODE steps' measured share of routed slots on
    held experts, not assumed."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    G, S = config["num_key_value_heads"], H - config["num_noise_heads"]
    qk, rope, dv = (config["head_dim"], config["qk_rope_head_dim"],
                    config["v_head_dim"])
    rq, rank = config["q_lora_rank"], config["kv_lora_rank"]
    n = config["mhc_expansion_rate"]
    layers, dense = config["num_hidden_layers"], \
        config["n_dense_first_layers"]
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    gate = S * dv
    attention = (D * (rq + rank + rope + S + gate) + rq * H * qk
                 + rank * G * (qk - rope + dv) + gate * D) * w \
        + (rq + rank) * f32
    outs = 2 * n + n * n
    mixer = n * D * outs * w + (n * D + 3 + outs + D) * f32
    poly = 4 * f32
    mlp = 3 * D * config["intermediate_size"] * w + poly
    F = config["moe_intermediate_size"]
    expert = 3 * D * F * w + poly
    moe_fixed = D * config["router_experts"] * w + expert     # + the shared
    n_moe = layers - dense
    n_full = sum(is_full(config, i) for i in range(layers))
    row = (rank + rope) * w
    mean_len = prompt_tokens + new_tokens / 2.0
    total = layers * (attention + 2 * mixer) + dense * mlp \
        + n_moe * moe_fixed
    total += (config["vocab_size"] + 1) * D * w + D * f32
    total += (layers - n_full) * min(config["sliding_window"], mean_len) * row
    total += n_full * mean_len * row
    total += held_share * config["experts_top_k"] * n_moe * expert
    return float(total)
