"""The ``trinity`` kind: a language model of grouped-query attention over
window and full layers mixed (a banded causal kernel and a causal one, a
ring and a buffer both written by a chunked prefill), a gated sandwich-norm
block and routed experts beside a shared one, rewriting a VERY long prompt
in front of a UNET image model. The cell's denoise step is the image leg's
(the configuration's file carries that leg's ``unet``/``vae`` blocks and
pinned ``step_flops``), so ``step_call`` is the UNet's; the language
model's own programs are built by ``cdtbench/parity_trinity.py``. The
counts the roofline shares divide by live here, with the benchmark:
``prefill_flops`` (``trinity_prefill_mfu_pct``), ``attention_core_flops``
by kind of layer (``trinity_full_core_mxu_pct``,
``trinity_window_core_mxu_pct``) and ``decode_bytes_per_token``
(``trinity_decode_hbm_pct``). Each counts what the program MUST do,
whatever implements it."""

from __future__ import annotations

from cdtbench.kinds import unet
from cdtbench.kinds.jamba import hbm_peak  # noqa: F401  the peaks table's bandwidth, for the readers
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph

# bytes a parameter, as the configuration holds them
_BYTES = {"bfloat16": 2, "float32": 4}
# the names the device trace gives the two kernels' operations (the jitted
# functions around the one pallas_call body: ops/flash_latent.py)
KERNELS = {"full": r"^gqa_causal_mha", "window": r"^gqa_window_mha"}


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_trinity "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


def layer_counts(config: dict) -> dict:
    """``{"full": n, "window": n}`` of the layers kept."""
    kinds = config["layer_types_kept"]
    full = sum(kind == "full_attention" for kind in kinds)
    return {"full": full, "window": len(kinds) - full}


def _attention_params(config: dict) -> int:
    """One layer's attention matrices (norm weights apart): ``[q | k | v |
    gate]`` and the output projection."""
    D, d = config["hidden_size"], config["head_dim"]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    return D * 2 * (H + G) * d + H * d * D


def attended_pairs(config: dict, prompt_tokens: int, kind: str) -> float:
    """(query, key) pairs ONE head of ONE layer of ``kind`` attends in a
    prefill: every key ``≤`` the query, and on a window layer at most
    ``sliding_window`` of them (the query's own included)."""
    T = prompt_tokens
    if kind == "full":
        return T * (T + 1) / 2.0
    seen = min(T, config["sliding_window"])
    return seen * (seen + 1) / 2.0 + (T - seen) * config["sliding_window"]


def attention_core_flops(config: dict, prompt_tokens: int,
                         kind: str) -> float:
    """The blocked kernel's algorithmic operations in ONE prefill over the
    layers of ``kind`` (``full``: causal pairs; ``window``: banded pairs),
    each pair once: ``2·d`` for the logit and ``2·d`` for the value, a
    head. A masked half of a diagonal or band-edge block, a re-read tile or
    a skipped block's grid step is the kernel's cost, not its work."""
    return float(layer_counts(config)[kind] * config["num_attention_heads"]
                 * attended_pairs(config, prompt_tokens, kind)
                 * 4 * config["head_dim"])


def prefill_flops(config: dict, prompt_tokens: int,
                  held_slots: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: per layer the
    attention's projections and its core (causal pairs once, banded pairs
    once); the dense FFN; per expert layer the router, the shared expert
    and ``held_slots`` (one request's routed slots that fell on held
    experts, all expert layers together, as the program counted them) rows
    of one expert — never the padded rows of a grouped tile; the head at
    ONE position."""
    T, D = prompt_tokens, config["hidden_size"]
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    expert = 3 * D * config["moe_intermediate_size"]
    total = layers * 2.0 * T * _attention_params(config)
    total += sum(attention_core_flops(config, T, kind)
                 for kind in ("full", "window"))
    total += dense * 2.0 * T * 3 * D * config["intermediate_size"]
    total += (layers - dense) * 2.0 * T * (
        D * config["router_experts"]
        + config["num_shared_experts"] * expert)
    total += 2.0 * held_slots * expert
    total += 2.0 * config["vocab_size"] * D
    return float(total)


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in
    the configuration's file: every weight outside the routed experts once
    (attention, the dense FFN, routers and their biases, shared experts,
    the four norms a layer), the head's slice once and one row of the
    embedding, the VALID key and value rows of every layer (a full layer's
    buffer at its mean length over the request's decode, a window layer's
    ring), and ``held_share`` x top-k x expert layers routed experts.
    Writes and the two rows of the rope table are left out (a few KB).
    ``held_share`` is the DECODE steps' measured share of routed slots on
    held experts, not assumed."""
    D, d = config["hidden_size"], config["head_dim"]
    G = config["num_key_value_heads"]
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    attention = _attention_params(config) * w + 2 * d * f32
    expert = 3 * D * config["moe_intermediate_size"] * w
    moe_fixed = D * config["router_experts"] * w \
        + config["router_experts"] * f32 \
        + config["num_shared_experts"] * expert
    n_moe = layers - dense
    counts = layer_counts(config)
    mean_len = prompt_tokens + new_tokens / 2.0
    total = layers * (attention + 4 * D * f32) \
        + dense * 3 * D * config["intermediate_size"] * w \
        + n_moe * moe_fixed
    total += (config["vocab_size"] + 1) * D * w + D * f32
    row = 2 * G * d * w                                  # a key and a value
    total += counts["full"] * mean_len * row
    total += counts["window"] * min(mean_len, config["sliding_window"]) * row
    total += held_share * config["num_experts_per_tok"] * n_moe * expert
    return float(total)


def moved(ctx: dict, series: str, match: dict,
          field: str = "value") -> float:
    """How far ``series`` (its samples matching ``match``; a counter's
    ``value``, a histogram's ``sum``) moved over the window: what the
    cell's counter and clock readers divide."""
    from cdtbench.readers import total

    cell = ctx["cell"]
    return (total(ctx["closed"], series, match, field, cell)
            - total(ctx["opened"], series, match, field, cell))


def core_mxu_pct(ctx: dict, kind: str):
    """What ``trinity_full_core_mxu_pct`` / ``trinity_window_core_mxu_pct``
    read: the kernel of ``kind``'s algorithmic operations over the compute
    peak and the DEVICE seconds under its name in the traced request, in
    percent; None where no such operation ran."""
    import re

    from cdtbench.flops import peak_flops

    cell = ctx["cell"]
    if cell.config.get("kind") != "trinity" or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu":
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    seconds = sum(s for op, s in ctx["trace"]["op_seconds"].items()
                  if re.search(KERNELS[kind], op))
    if not program or not program["count"] or not seconds:
        return None
    need = program["count"] * attention_core_flops(
        cell.config, request_sizes(cell)[0], kind)
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds
