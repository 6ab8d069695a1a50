"""The ``brumby`` kind: a language model whose every layer is POWER RETENTION
— a gated, normalised linear recurrence over the degree-2 symmetric power of
every key; a cache of recurrent states and NO K/V row, written by a chunked
prefill whose last chunk is padded under a gate that is data — in the block
of a dense transformer, rewriting a prompt that fills its context in front of
a UNET image model. The cell's denoise step is the image leg's (the
configuration's file carries that leg's ``unet``/``vae`` blocks and pinned
``step_flops``), so ``step_call`` is the UNet's; the language model's own
programs are built by ``cdtbench/parity_brumby.py``. The counts the roofline
shares divide by live here, with the benchmark — ``prefill_flops``
(``brumby_prefill_mfu_pct``), ``retention_flops``
(``brumby_retention_mxu_pct``) and ``decode_bytes_per_token``
(``brumby_decode_hbm_pct``), each what the program MUST do by the model's
rule, whatever implements it — and so do the cell's readers that are not
plain data (``layer_metrics/brumby_*.py`` only name one of them).
``cdtbench/BRUMBY.md`` derives the counts."""

from __future__ import annotations

import re

from cdtbench.kinds import glm, unet
from cdtbench.kinds.jamba import hbm_peak
from cdtbench.kinds.keye import _as_glm, _peak_share
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import moved

KIND = "brumby"
SCOPE = "llm_retention"     # a plain named scope below cdt.llm_attn
PROGRAMS = ("llm_prefill", "llm_decode")
KEYS = "cdt_llm_attn_keys_total"
TOKENS = "cdt_llm_tokens_total"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_brumby "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def _heads(config: dict) -> tuple:
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"])


def matrix_params(config: dict) -> int:
    """One layer's matrices: W_q, W_k, W_v, W_o, the gate's, the SwiGLU's
    three."""
    D, F, (H, G, d) = (config["hidden_size"], config["intermediate_size"],
                       _heads(config))
    return D * (H + 2 * G) * d + H * d * D + D * G + 3 * D * F


def layer_parameters(config: dict) -> int:
    """Every parameter of one layer: the matrices, the gate's bias, the two
    head norms and the two norms."""
    _, G, d = _heads(config)
    return matrix_params(config) + G + 2 * d + 2 * config["hidden_size"]


def parameters(config: dict) -> int:
    """Every held parameter of the cut, from the configuration's sizes: the
    layers, both ends of the vocabulary and the final norm."""
    D = config["hidden_size"]
    return config["num_hidden_layers"] * layer_parameters(config) \
        + 2 * config["vocab_size"] * D + D


def state_width(config: dict) -> int:
    """``D`` as the function has it: ``d (d + 1) / 2`` — 8256, NOT the 8320
    lanes the program holds."""
    d = config["head_dim"]
    return d * (d + 1) // 2


def retention_flops(config: dict, positions: float) -> float:
    """The state form's two products over ``positions`` folded into a state
    (tokens × layers, as the program counted them): every query head reads
    ``φ(q)ᵀ[S | z]`` and every K/V head adds ``φ(k)[v | 1]ᵀ``, ``2 · D ·
    (d + 1)`` each at the EXACT ``D``. No pair inside a block, no lane
    padding, no making of ``φ``: the same work whatever block, padding or
    kernel implements it, and less than any implementation does."""
    H, G, d = _heads(config)
    return float(positions * 2.0 * state_width(config) * (d + 1) * (H + G))


def prefill_flops(config: dict, tokens: float, positions: float) -> float:
    """The algorithmic operations of ONE ``llm_prefill``: the layers'
    matrices on the ``tokens`` the program says it ran, the retention count
    of the ``positions`` it folded, the untied head on the last position."""
    return float(config["num_hidden_layers"] * 2.0 * tokens
                 * matrix_params(config)
                 + retention_flops(config, positions)
                 + 2.0 * config["vocab_size"] * config["hidden_size"])


def decode_bytes_per_token(config: dict) -> float:
    """The bytes ONE decoded token must move through HBM, from the sizes in
    the configuration's file: every layer's weights and the head once
    (``llm.bytes`` less the embedding's table, of which one row is read) and
    every state read AND written once (float32; the same at every
    position)."""
    D, w = config["hidden_size"], 2
    cache = sum(config["llm"]["cache_bytes"].values())
    return float(config["llm"]["bytes"] - (config["vocab_size"] - 1) * D * w
                 + 2 * cache)


# --- the cell's readers that are not plain data -----------------------------


def _mine(ctx: dict) -> bool:
    return ctx["cell"].config.get("kind") == KIND


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
    """``brumby_share_pct``: seconds inside the language model's two
    programs over the client's wall seconds of the window's requests."""
    return glm.share_pct(_as_glm(ctx)) if _mine(ctx) else None


def decode_ms_per_token(ctx: dict):
    """``brumby_decode_ms_per_token``: host seconds inside ``llm_decode``
    over the tokens the cell's graph asks of it."""
    return glm.decode_ms_per_token(_as_glm(ctx)) if _mine(ctx) else None


def prefill_mfu_pct(ctx: dict):
    """``brumby_prefill_mfu_pct``: ``prefill_flops`` (the tokens and the
    positions folded as the program counted them in the window, a request)
    over the compute peak and the traced ``jit_llm_prefill``'s DEVICE time."""
    program = _traced_program(ctx, "llm_prefill")
    positions = program and _a_request(ctx, KEYS, {"layers": "^retention$"})
    tokens = positions and _a_request(ctx, TOKENS, {})
    if not tokens:
        return None
    return _peak_share(ctx, prefill_flops(ctx["cell"].config, tokens,
                                          positions),
                       program["seconds"] / program["count"])


def decode_hbm_pct(ctx: dict):
    """``brumby_decode_hbm_pct``: ``decode_bytes_per_token`` over the HBM
    peak and the traced ``jit_llm_decode``'s DEVICE time a token."""
    program = _traced_program(ctx, "llm_decode")
    if program is None:
        return None
    token_s = program["seconds"] / program["count"] \
        / request_sizes(ctx["cell"])[1]
    return 100.0 * decode_bytes_per_token(ctx["cell"].config) \
        / hbm_peak(ctx["device"]["kind"]) / token_s


_retention_seconds: dict = {}


def retention_seconds(ctx: dict):
    """DEVICE seconds (self times, mean over the chips) of the traced
    window's operations that carry the plain named scope ``llm_retention``
    in their name stack, by the program that ran them — ``{"llm_prefill":
    s, "llm_decode": s}``: ``kinds/glm.py: scope_seconds``'s way, told apart
    by program as ``device_layers.chip_report`` tells the ``cdt.<layer>``
    scopes. None without a trace or where no operation carries the scope
    (the parent; any cell of another kind)."""
    if not _mine(ctx) or ctx.get("trace") is None:
        return None
    from cdtbench import device_layers as dl

    out_dir = dl.ROOT / "chiprun_out" / "cdtbench" / ctx["cell"].name
    xplane = dl.find_xplane(out_dir / "profile")
    if xplane is None:
        return None
    key = str(dl._key(xplane))
    if key not in _retention_seconds:
        phases = ctx["cell"].config.get("trace_phases", {})
        found = {program: 0.0 for program in PROGRAMS}
        part = re.compile(r"/" + SCOPE + r"(?:/|$)")
        planes = [p for p in dl.read_space(xplane)
                  if p["lines"].get(dl.OPS_LINE)]
        for plane in planes:
            ops = {k: dl.describe(meta)
                   for k, meta in plane["metadata"].items()}
            programs = {}
            for k, _, _ in plane["lines"].get(dl.MODULES_LINE, ()):
                module = plane["metadata"][k]["name"]
                at = re.search(r"\((\d+)\)$", module)
                if at:
                    programs[int(at.group(1))] = dl.phase_of(module, phases)
            for op, own in dl.self_times(plane["lines"][dl.OPS_LINE]):
                program = programs.get(ops[op]["program_id"])
                if program in found and part.search(ops[op]["tf_op"]) \
                        and not ops[op]["control_flow"]:
                    found[program] += own * (dl.PS / 1e-9) / len(planes)
        _retention_seconds[key] = found if any(found.values()) else None
    return _retention_seconds[key]


def retention_pct(ctx: dict):
    """``brumby_retention_pct``: the scope's share of the two language
    programs' DEVICE seconds in the traced request."""
    found = retention_seconds(ctx)
    programs = [_traced_program(ctx, p) for p in PROGRAMS]
    if not found or None in programs:
        return None
    return 100.0 * sum(found.values()) / sum(p["seconds"] for p in programs)


def retention_mxu_pct(ctx: dict):
    """``brumby_retention_mxu_pct``: ``retention_flops`` of the positions
    the prefill folded (a request) over the compute peak and the scope's
    DEVICE seconds inside the traced ``jit_llm_prefill``."""
    program = _traced_program(ctx, "llm_prefill")
    found = program and retention_seconds(ctx)
    positions = found and _a_request(ctx, KEYS, {"layers": "^retention$"})
    if not positions or not found["llm_prefill"]:
        return None
    return _peak_share(ctx, program["count"] * retention_flops(
        ctx["cell"].config, positions), found["llm_prefill"])
