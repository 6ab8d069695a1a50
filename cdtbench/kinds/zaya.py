"""The ``zaya`` kind: a language model whose attention runs inside a
compressed latent behind two causal convolutions (K/V rows and three
recurrent tails written by a chunked prefill whose last chunk is padded), a
top-1 router that is an MLP carrying its state from layer to layer and an
expert layer that holds EVERY expert, rewriting a prompt that fills its
context in front of a UNET image model. The cell's denoise step is the image
leg's (the configuration's file carries that leg's ``unet``/``vae`` blocks
and pinned ``step_flops``), so ``step_call`` is the UNet's; the language
model's own programs are built by ``cdtbench/parity_zaya.py``. The counts the
roofline shares divide by live here, with the benchmark — ``prefill_flops``
(``zaya_prefill_mfu_pct``), ``core_flops`` (``zaya_cca_core_mxu_pct``) and
``expert_flops`` (``zaya_experts_mxu_pct``), each what the program MUST do by
the model's rule, whatever implements it — and so do the cell's readers that
are not plain data (``layer_metrics/zaya_*.py`` only name one of them).
``cdtbench/ZAYA.md`` derives the counts."""

from __future__ import annotations

import dataclasses

from cdtbench.kinds import glm, unet
from cdtbench.kinds.keye import _kernel_seconds, _peak_share
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import KERNELS, moved

KIND = "zaya"
CORE_KERNEL = KERNELS["full"]        # gqa_causal_mha.*: the fifth rewriter's
EXPERTS_LAYER = "llm_experts"        # a cdt.<layer> device scope
KEYS = "cdt_llm_attn_keys_total"
SLOTS = "cdt_llm_expert_slots_total"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_zaya "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def _heads(config: dict) -> tuple:
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"])


def attention_params(config: dict) -> int:
    """One layer's attention matrices: W_q and W_k (the latents), W_v1 and
    W_v2, W_o out of the QUERY latent."""
    D, (H, G, d) = config["hidden_size"], _heads(config)
    return D * (H + G) * d + D * 2 * d + H * d * D


def conv_params(config: dict) -> int:
    """One layer's two convolutions: depthwise taps and bias over the
    ``(H + G) · d`` latent channels; a ``d × d`` mix a head a tap and bias."""
    H, G, d = _heads(config)
    Z = (H + G) * d
    return Z * config["cca_time0"] + Z \
        + (H + G) * config["cca_time1"] * d * d + Z


def router_params(config: dict) -> int:
    """One layer's router: the down-projection with bias, the scale on the
    state of the layer before, its norm, two hidden layers with bias, the
    output matrix and the selection bias."""
    D, R, E = (config["hidden_size"], config["router_hidden_size"],
               config["num_experts"])
    return D * R + R + R + R + 2 * (R * R + R) + R * E + E


def expert_params(config: dict) -> int:
    """ONE expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_parameters(config: dict) -> int:
    """Every parameter of one layer: attention, convolutions, a temperature
    a K/V head, the router, the held experts, two norms and the two
    sublayers' four residual vectors each."""
    D = config["hidden_size"]
    return attention_params(config) + conv_params(config) \
        + config["num_key_value_heads"] + router_params(config) \
        + config["num_experts"] * expert_params(config) + 2 * D + 8 * D


def parameters(config: dict) -> int:
    """Every held parameter of the cut, from the configuration's sizes: the
    layers, the embedding (which is the head) and the final norm."""
    D = config["hidden_size"]
    return config["num_hidden_layers"] * layer_parameters(config) \
        + config["vocab_size"] * D + D


def core_flops(config: dict, pairs: float) -> float:
    """The causal core's matrix operations over ``pairs`` (query, key) pairs
    a head (summed over the layers): ``2·head_dim`` for the logit and
    ``2·head_dim`` for the value of every QUERY head — the latent's 1024
    columns, not the stream's 2048. A masked half of a diagonal block or a
    skipped block's grid step is the kernel's cost, not its work."""
    H, _, d = _heads(config)
    return float(pairs * H * 4 * d)


def expert_flops(config: dict, slots: float) -> float:
    """``slots`` routed rows through one expert each: ``2 · 3 · D · F`` a
    row. A tile's padding is the form's cost, not its work."""
    return float(2.0 * slots * expert_params(config))


def prefill_flops(config: dict, prompt_tokens: int, pairs: float,
                  held_slots: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: per layer the
    attention's projections, the grouped convolution's mixes and the
    router's products on every token; the causal ``pairs`` (a head, all
    layers together) and ``held_slots`` (routed rows, all layers together),
    both as the program counted them; the tied head on the last position."""
    T, D = prompt_tokens, config["hidden_size"]
    H, G, d = _heads(config)
    R, E = config["router_hidden_size"], config["num_experts"]
    per_token = attention_params(config) \
        + (H + G) * config["cca_time1"] * d * d \
        + D * R + 2 * R * R + R * E
    return float(config["num_hidden_layers"] * 2.0 * T * per_token
                 + core_flops(config, pairs)
                 + expert_flops(config, held_slots)
                 + 2.0 * config["vocab_size"] * D)


# --- the cell's readers that are not plain data -----------------------------


def _mine(ctx: dict) -> bool:
    return ctx["cell"].config.get("kind") == KIND


def _traced_prefill(ctx: dict):
    """The traced request's ``llm_prefill`` in a cell of this kind on a TPU,
    else None."""
    if not _mine(ctx) or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu" or not ctx["requests"]:
        return None
    program = ctx["trace"]["phase_seconds"].get("llm_prefill")
    return program if program and program["count"] else None


def _a_request(ctx: dict, series: str, match: dict) -> float:
    return moved(ctx, series, {**match, "phase": "^prefill$"}) \
        / ctx["requests"]


def decode_ms_per_token(ctx: dict):
    """``zaya_decode_ms_per_token``: host seconds inside ``llm_decode`` over
    the tokens the cell's graph asks of it — ``kinds/glm.py``'s reader,
    which reads nothing of a configuration but its kind."""
    if not _mine(ctx):
        return None
    cell = ctx["cell"]
    return glm.decode_ms_per_token({**ctx, "cell": dataclasses.replace(
        cell, config={**cell.config, "kind": glm.KIND})})


def prefill_mfu_pct(ctx: dict):
    """``zaya_prefill_mfu_pct``: ``prefill_flops`` (the causal pairs and the
    routed slots as the program counted them in the window, a request) over
    the compute peak and the traced ``jit_llm_prefill``'s DEVICE time."""
    program = _traced_prefill(ctx)
    pairs = program and _a_request(ctx, KEYS, {"layers": "^cca$"})
    if not pairs:
        return None
    need = prefill_flops(ctx["cell"].config, request_sizes(ctx["cell"])[0],
                         pairs, _a_request(ctx, SLOTS, {"where": "^held$"}))
    return _peak_share(ctx, need, program["seconds"] / program["count"])


def cca_core_mxu_pct(ctx: dict):
    """``zaya_cca_core_mxu_pct``: ``core_flops`` of the causal pairs the
    prefill counted (a request) over the compute peak and the DEVICE seconds
    under the causal kernel's name in the traced request; None where no such
    operation ran."""
    program = _traced_prefill(ctx)
    seconds = program and _kernel_seconds(ctx, CORE_KERNEL)
    pairs = seconds and _a_request(ctx, KEYS, {"layers": "^cca$"})
    if not pairs:
        return None
    return _peak_share(ctx, program["count"]
                       * core_flops(ctx["cell"].config, pairs), seconds)


def experts_mxu_pct(ctx: dict):
    """``zaya_experts_mxu_pct``: ``expert_flops`` of the routed slots the
    prefill counted (a request) over the compute peak and the DEVICE seconds
    under ``cdt.llm_experts`` in the traced ``jit_llm_prefill``; None where
    the trace shows no such scope."""
    from cdtbench import device_layers

    program = _traced_prefill(ctx)
    if program is None:
        return None
    row = (device_layers.of_run(ctx) or {}).get("phases", {}).get(
        "llm_prefill", {}).get(EXPERTS_LAYER)
    held = _a_request(ctx, SLOTS, {"where": "^held$"})
    if not row or not row["seconds"] or not held:
        return None
    return _peak_share(ctx, program["count"]
                       * expert_flops(ctx["cell"].config, held),
                       row["seconds"])
