"""Language-model presets (``kind: "llm"``): a prompt rewriter in front of
an image model. The cell's denoise step is the IMAGE leg's (the
configuration's file carries that leg's ``dit``/``vae`` blocks and pinned
``step_flops``), so ``step_call`` is the DiT's; the language model's own
programs are built by ``cdtbench/parity.py``. ``decode_bytes_per_token``
is the numerator of ``llm_decode_hbm_pct``."""

from __future__ import annotations

from cdtbench.kinds import dit

# bytes a parameter, as the configuration holds them
_BYTES = {"bfloat16": 2, "float32": 4}


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return dit.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "an llm cell's programs are llm_prefill and llm_decode: compile "
        "them off-chip with `python -m cdtbench.parity --workload "
        f"{cell.name} --compile-only` (offchip.py builds image models)")


def request_sizes(cell) -> tuple[int, int]:
    """``(prompt_tokens, new_tokens)`` of the cell's rewrite node, from the
    graph the cell posts: the one place they are written."""
    node_id, _ = cell.traffic["nodes"]["prompt"]
    inputs = cell.graph[node_id]["inputs"]
    return int(inputs["prompt_tokens"]), int(inputs["new_tokens"])


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts
    once (mixers, dense FFNs, routers, shared experts, norms), the head's
    slice once and one row of the embedding, the KDA states (read and
    written, float32) and convolution tails, the latent cache at its mean
    length over the request's decode, and ``held_share`` x 8 slots x
    expert layers routed experts. Writes other than the state's are left
    out (a few KB). ``held_share`` is the DECODE steps' measured share
    of routed slots on held experts, not assumed."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    dk = config["head_dim"]
    layers, group = config["num_hidden_layers"], config["layer_group_size"]
    dense = config["first_k_dense_replace"]
    w = _BYTES[config["llm"]["dtype"]]
    s = _BYTES["float32"]                               # the KDA state
    kw = H * dk
    K = config["short_conv_kernel_size"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    kda = (D * (4 * kw + 2 * H) + kw * D) * w + (3 * K * kw + H + kw + dk) * 4
    mla = (D * (H * qk + rank + rope + H)
           + rank * H * (config["qk_nope_head_dim"] + config["v_head_dim"])
           + H * config["v_head_dim"] * D) * w + (rank + qk + rope) * 4
    ffn = 3 * D * config["intermediate_size"] * w
    F, Fs = (config["moe_intermediate_size"],
             config["moe_shared_expert_intermediate_size"])
    moe_fixed = (D * config["router_experts"] + 3 * D * Fs) * w \
        + config["router_experts"] * 4
    expert = 3 * D * F * w
    n_mla = sum((i + 1) % group == 0 for i in range(layers))
    n_kda = layers - n_mla
    n_moe = layers - dense
    mean_len = prompt_tokens + new_tokens / 2.0
    total = n_kda * kda + n_mla * mla + dense * ffn + n_moe * moe_fixed
    total += layers * 2 * D * 4 + D * 4                # norms
    total += (config["vocab_size"] + 1) * D * w         # head + one row
    total += n_kda * (2 * H * dk * dk * s + 2 * (K - 1) * 3 * kw * w)
    total += n_mla * mean_len * (rank + rope) * w
    total += held_share * config["num_experts_per_tok"] * n_moe * expert
    return float(total)
