"""The ``mimo`` kind: a language model of window layers (128 keys, a learned
sink in the softmax, a ring SHORTER than the prefill chunk) and full layers
mixed — each kind with its own K/V head count and rope base, keys 192 wide and
values 128 — over routed experts with no shared one, rewriting a VERY long
prompt in front of a UNET image model. The cell's denoise step is the image
leg's (the configuration's file carries that leg's ``unet``/``vae`` blocks and
pinned ``step_flops``), so ``step_call`` is the UNet's; the language model's
own programs are built by ``cdtbench/parity_mimo.py``. The counts the roofline
shares divide by live here, with the benchmark — ``prefill_flops``
(``mimo_prefill_mfu_pct``), ``attention_core_flops``
(``mimo_full_core_mxu_pct``) and ``decode_bytes_per_token``
(``mimo_decode_hbm_pct``), each what the program MUST do by the model's rule,
whatever implements it: a (query, key) pair is ``2 · (192 + 128)`` operations a
head, the EXACT widths, never the 256 a 128-wide matrix unit pads a 192-wide
contraction to — and so do the cell's readers that are not plain data
(``layer_metrics/mimo_*.py`` only name one of them). ``cdtbench/MIMO.md``
derives the counts."""

from __future__ import annotations

import re

from cdtbench.kinds import glm, unet
from cdtbench.kinds.jamba import hbm_peak
from cdtbench.kinds.keye import _kernel_seconds, _peak_share
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import moved

KIND = "mimo"
_BYTES = {"bfloat16": 2, "float32": 4}
# the name the device trace gives the full layers' kernel (the jitted function
# around the pallas_call: ops/gqa_sink_attention.py) and the plain named scopes
# of the two cores below cdt.llm_attn (models/llm_mimo.py)
FULL_KERNEL = r"^gqa_wide_causal_mha"
SCOPES = {"full": "llm_full_core", "window": "llm_swa_core"}
KEYS = "cdt_llm_attn_keys_total"
SLOTS = "cdt_llm_expert_slots_total"
TOKENS = "cdt_llm_tokens_total"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_mimo "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def layer_counts(config: dict) -> dict:
    """``{"full": n, "window": n}`` of the layers kept."""
    full = sum(kind == "full_attention" for kind in config["layer_types"])
    return {"full": full, "window": len(config["layer_types"]) - full}


def kv_heads(config: dict, kind: str) -> int:
    return config["num_key_value_heads" if kind == "full"
                  else "swa_num_key_value_heads"]


def attention_params(config: dict, kind: str) -> int:
    """One layer's attention matrices: ``[q | k | v]`` and the output
    projection out of the VALUE width (a window layer's 64 sinks apart)."""
    D, H, G = (config["hidden_size"], config["num_attention_heads"],
               kv_heads(config, kind))
    dk, dv = config["head_dim"], config["v_head_dim"]
    return D * ((H + G) * dk + G * dv) + H * dv * D


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def parameters(config: dict) -> int:
    """Every held parameter of the cut, from the configuration's sizes."""
    D, counts = config["hidden_size"], layer_counts(config)
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    total = sum(counts[kind] * attention_params(config, kind)
                for kind in counts)
    total += counts["window"] * config["num_attention_heads"]      # sinks
    total += layers * 2 * D + D                                    # norms
    total += dense * 3 * D * config["intermediate_size"]
    total += (layers - dense) * (
        D * config["router_experts"] + config["router_experts"]
        + config["n_routed_experts"] * expert_params(config))
    return total + 2 * config["vocab_size"] * D


def pair_flops(config: dict) -> float:
    """A (query, key) pair of every head: ``2 · head_dim`` for the logit and
    ``2 · v_head_dim`` for the value — 640 a head, EXACT."""
    return float(config["num_attention_heads"]
                 * 2 * (config["head_dim"] + config["v_head_dim"]))


def attention_core_flops(config: dict, pairs: float) -> float:
    """The operations of ``pairs`` (query, key) pairs a head (summed over the
    layers of a kind), each pair once. A masked half of a diagonal block, a
    band's second block of keys or a skipped block's grid step is the form's
    cost, not its work."""
    return float(pairs * pair_flops(config))


def prefill_flops(config: dict, tokens: float, pairs: float,
                  held_slots: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: per layer the
    attention's projections on the ``tokens`` the program ran; ``pairs`` (a
    head, both kinds of layer together) and ``held_slots`` (routed rows, all
    expert layers together), all three as the program counted them; the dense
    FFN and the routers on every token; the head at ONE position."""
    D, counts = config["hidden_size"], layer_counts(config)
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    per_token = sum(counts[kind] * attention_params(config, kind)
                    for kind in counts) \
        + dense * 3 * D * config["intermediate_size"] \
        + (layers - dense) * D * config["router_experts"]
    return float(2.0 * tokens * per_token
                 + attention_core_flops(config, pairs)
                 + 2.0 * held_slots * expert_params(config)
                 + 2.0 * config["vocab_size"] * D)


def decode_bytes_per_token(config: dict, held_share: float,
                           prompt_tokens: int, new_tokens: int) -> float:
    """The bytes ONE decoded token must read from HBM, from the sizes in the
    configuration's file: every weight outside the routed experts once
    (attention by kind with a window layer's sinks, the dense FFN, routers
    and their biases, two norms a layer), the head's slice once and one row of
    the embedding, the VALID key and value rows of every layer (a full
    layer's buffer at its mean length over the request's decode, a window
    layer's ring), and ``held_share`` x top-k x expert layers routed experts.
    Writes and the rows of the rope tables are left out (a few KB).
    ``held_share`` is the DECODE steps' measured share of routed slots on held
    experts, not assumed."""
    D, counts = config["hidden_size"], layer_counts(config)
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    w, f32 = _BYTES[config["llm"]["dtype"]], _BYTES["float32"]
    n_moe = layers - dense
    total = sum(counts[kind] * attention_params(config, kind) * w
                for kind in counts)
    total += counts["window"] * config["num_attention_heads"] * f32
    total += layers * 2 * D * f32 + D * f32
    total += dense * 3 * D * config["intermediate_size"] * w
    total += n_moe * (D * config["router_experts"] * w
                      + config["router_experts"] * f32)
    total += (config["vocab_size"] + 1) * D * w
    mean_len = prompt_tokens + new_tokens / 2.0
    rows = {"full": mean_len,
            "window": min(mean_len, config["sliding_window"])}
    total += sum(counts[kind] * rows[kind] * kv_heads(config, kind)
                 * (config["head_dim"] + config["v_head_dim"]) * w
                 for kind in counts)
    total += held_share * config["num_experts_per_tok"] * n_moe \
        * expert_params(config) * w
    return float(total)


# --- the cell's readers that are not plain data -----------------------------


def _mine(ctx: dict) -> bool:
    return ctx["cell"].config.get("kind") == KIND


def _as_glm(ctx: dict) -> dict:
    """``ctx`` with the cell marked as of the ``glm`` kind: that kind's
    readers of the clock read nothing of a configuration but its kind."""
    import dataclasses

    cell = ctx["cell"]
    return {**ctx, "cell": dataclasses.replace(
        cell, config={**cell.config, "kind": glm.KIND})}


def _traced_program(ctx: dict, phase: str):
    """The traced request's program of ``phase`` in a cell of this kind on a
    TPU, else None."""
    if not _mine(ctx) or ctx["trace"] is None \
            or ctx["device"]["platform"] != "tpu" or not ctx["requests"]:
        return None
    program = ctx["trace"]["phase_seconds"].get(phase)
    return program if program and program["count"] else None


def _a_request(ctx: dict, series: str, match: dict) -> float:
    return moved(ctx, series, {**match, "phase": "^prefill$"}) \
        / ctx["requests"]


def share_pct(ctx: dict):
    """``mimo_share_pct``: seconds inside the language model's two programs
    over the client's wall seconds of the window's requests."""
    return glm.share_pct(_as_glm(ctx)) if _mine(ctx) else None


def decode_ms_per_token(ctx: dict):
    """``mimo_decode_ms_per_token``: host seconds inside ``llm_decode`` over
    the tokens the cell's graph asks of it."""
    return glm.decode_ms_per_token(_as_glm(ctx)) if _mine(ctx) else None


def prefill_mfu_pct(ctx: dict):
    """``mimo_prefill_mfu_pct``: ``prefill_flops`` (the tokens, the pairs of
    both kinds and the held slots as the program counted them in the window, a
    request) over the compute peak and the traced ``jit_llm_prefill``'s DEVICE
    time."""
    program = _traced_program(ctx, "llm_prefill")
    pairs = program and _a_request(ctx, KEYS, {"layers": "^(full|window)$"})
    tokens = pairs and _a_request(ctx, TOKENS, {})
    if not tokens:
        return None
    need = prefill_flops(ctx["cell"].config, tokens, pairs,
                         _a_request(ctx, SLOTS, {"where": "^held$"}))
    return _peak_share(ctx, need, program["seconds"] / program["count"])


def decode_hbm_pct(ctx: dict):
    """``mimo_decode_hbm_pct``: ``decode_bytes_per_token`` (the decode steps'
    counted held share) over the HBM peak and the traced ``jit_llm_decode``'s
    DEVICE time a token."""
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


def full_core_mxu_pct(ctx: dict):
    """``mimo_full_core_mxu_pct``: ``attention_core_flops`` of the causal
    pairs the prefill counted for the full layers (a request) over the compute
    peak and the DEVICE seconds under the kernel's name in the traced request;
    None where no such operation ran. A 192-wide contraction is two passes of
    a 128-wide matrix unit: 83.3 is what the hardware allows."""
    program = _traced_program(ctx, "llm_prefill")
    seconds = program and _kernel_seconds(ctx, FULL_KERNEL)
    pairs = seconds and _a_request(ctx, KEYS, {"layers": "^full$"})
    if not pairs:
        return None
    return _peak_share(ctx, program["count"] * attention_core_flops(
        ctx["cell"].config, pairs), seconds)


_scope_seconds: dict = {}


def scope_seconds(ctx: dict):
    """DEVICE seconds (self times, mean over the chips) of the traced window's
    operations by the plain named scope of :data:`SCOPES` in their name stack
    — ``{"full": s, "window": s}``: ``kinds/glm.py: scope_seconds``'s way with
    this kind's scopes. None without a trace or where no operation carries
    such a scope (the parent; any cell of another kind)."""
    if not _mine(ctx) or ctx.get("trace") is None:
        return None
    from cdtbench import device_layers as dl

    out_dir = dl.ROOT / "chiprun_out" / "cdtbench" / ctx["cell"].name
    xplane = dl.find_xplane(out_dir / "profile")
    if xplane is None:
        return None
    key = str(dl._key(xplane))
    if key not in _scope_seconds:
        kinds = {scope: kind for kind, scope in SCOPES.items()}
        found = {kind: 0.0 for kind in SCOPES}
        part = re.compile(r"/(" + "|".join(kinds) + r")(?:/|$)")
        planes = [p for p in dl.read_space(xplane)
                  if p["lines"].get(dl.OPS_LINE)]
        for plane in planes:
            ops = {k: dl.describe(meta)
                   for k, meta in plane["metadata"].items()}
            for op, own in dl.self_times(plane["lines"][dl.OPS_LINE]):
                hit = part.search(ops[op]["tf_op"])
                if hit and not ops[op]["control_flow"]:
                    found[kinds[hit.group(1)]] += \
                        own * (dl.PS / 1e-9) / len(planes)
        _scope_seconds[key] = found if any(found.values()) else None
    return _scope_seconds[key]


def core_pct(ctx: dict, kinds: tuple):
    """``mimo_window_core_pct`` (``("window",)``) and ``mimo_attn_core_pct``
    (both): the share of the window's busy DEVICE seconds under the cores'
    scopes."""
    found = scope_seconds(ctx)
    if not found or not ctx["trace"].get("busy_s"):
        return None
    seconds = sum(found[kind] for kind in kinds)
    return 100.0 * seconds / ctx["trace"]["busy_s"] if seconds else None
