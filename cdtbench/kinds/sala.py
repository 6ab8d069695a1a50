"""The ``sala`` kind: a language model of decayed linear attention
(``lightning-attn``) and grouped-query attention over a SELECTION of key
blocks (``minicpm4``: InfLLM-v2) mixed, a dense FFN on every layer and an
untied head, rewriting a very long prompt in front of a UNET image model.
The cell's denoise step is the image leg's (the configuration's file
carries that leg's ``unet``/``vae`` blocks and pinned ``step_flops``), so
``step_call`` is the UNet's; the language model's own programs are built by
``cdtbench/parity_sala.py``. The counts the roofline shares divide by live
here, with the benchmark — ``prefill_flops`` (``sala_prefill_mfu_pct``),
``attention_core_flops`` (``sala_sparse_core_mxu_pct``) and
``decode_bytes_per_token`` (``sala_decode_hbm_pct``), each what the program
MUST do by the model's rule, whatever implements it — and so do the cell's
readers that are not plain data (``layer_metrics/sala_*.py`` only name one
of them). ``cdtbench/SALA.md`` derives the counts."""

from __future__ import annotations

import re

from cdtbench.kinds import unet
from cdtbench.kinds.jamba import hbm_peak
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph
from cdtbench.kinds.trinity import moved

KIND = "sala"
_BYTES = {"bfloat16": 2, "float32": 4}
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# the name the device trace gives the table-driven kernel's operations (the
# jitted function around its pallas_call: ops/block_select_attention.py)
SPARSE_KERNEL = r"^block_select_mha"
# plain named scopes below cdt.llm_attn (models/llm_sala.py): a component
# of an operation's name stack (tf_op)
SCOPES = ("select", "sparse_core", "lightning")
KEYS = "cdt_llm_attn_keys_total"
SECONDS = "cdt_pipeline_execute_seconds"


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_sala "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


# --- the counts -------------------------------------------------------------


def layer_counts(config: dict) -> tuple[int, int]:
    """``(lightning layers, sparse layers)`` of the depth held."""
    kinds = config["mixer_types"]
    return kinds.count(LIGHTNING), kinds.count(SPARSE)


def _matrix_params(config: dict) -> tuple[int, int, int]:
    """Multiplied weights a token of ``(a lightning mixer, a sparse mixer,
    an FFN)``: q, k, v, the output gate and o; norms are not products."""
    D = config["hidden_size"]
    wide = config["lightning_nh"] * config["lightning_head_dim"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return 5 * D * wide, D * (3 * q + 2 * kv), \
        3 * D * config["intermediate_size"]


def selects(config: dict, total: int) -> bool:
    """Does a request of ``total`` positions pass ``dense_len``?"""
    return total > config["dense_len"]


def selected_rows(config: dict, first: int, last: int, total: int) -> float:
    """Rows ONE head of a sparse layer reads for the queries at positions
    ``first … last − 1`` of a request that reaches ``total`` positions, by
    the model's rule: the rows at or below the query in the ``topk + window
    / block`` blocks it selects (its own block in part), every row at or
    below it where the request is within ``dense_len`` or fewer blocks
    exist than a table holds."""
    import numpy as np

    bs = config["block_size"]
    table = config["topk"] + config["window_size"] // bs
    t = np.arange(first, last, dtype=np.int64)
    if not selects(config, total):
        return float((t + 1).sum())
    return float(np.where(t // bs + 1 > table,
                          (table - 1) * bs + t % bs + 1, t + 1).sum())


def scored_windows(config: dict, first: int, last: int, total: int) -> float:
    """Compressed keys ONE head scores for those queries: the windows
    whose ``kernel_size`` rows all lie at or below the query."""
    if not selects(config, total):
        return 0.0
    import numpy as np

    ks, st = config["kernel_size"], config["kernel_stride"]
    t = np.arange(first, last, dtype=np.int64)
    return float(np.maximum((t + 1 - ks) // st + 1, 0).sum())


def attention_core_flops(config: dict, prompt_tokens: int,
                         total: int) -> float:
    """The sparse layers' attention core in ONE prefill: the SELECTED
    (query, key) pairs by the rule (:func:`selected_rows`) a head a layer,
    times ``2·d`` for the logit and ``2·d`` for the value. A tile's
    padding, a masked half of the own block, a union's blocks that a query
    did not select, a re-read tile or a skipped grid step is the kernel's
    cost, not its work."""
    n_sparse = layer_counts(config)[1]
    return n_sparse * config["num_attention_heads"] * 4.0 \
        * config["head_dim"] * selected_rows(config, 0, prompt_tokens, total)


def prefill_flops(config: dict, prompt_tokens: int, total: int) -> float:
    """The algorithmic MATRIX operations of ONE ``llm_prefill``: every
    layer's products for every token; the sparse layers' selected pairs
    once and their scores against the complete compressed keys; a
    lightning layer's recurrence as what it must do a token a head — ``k
    vᵀ`` into the state and ``q S`` out of it, ``4·d²`` — whatever chunk
    form runs (the chunk form's own ``QKᵀ`` and ``AV`` inside a block are
    the implementation's); the head at ONE position. Top-k, pooling,
    softmax and the decay are vector work: not counted."""
    lightning, sparse, ffn = _matrix_params(config)
    n_light, n_sparse = layer_counts(config)
    T, d = prompt_tokens, config["lightning_head_dim"]
    per_token = n_light * (lightning + ffn) + n_sparse * (sparse + ffn)
    total_flops = 2.0 * T * per_token
    total_flops += attention_core_flops(config, T, total)
    total_flops += n_sparse * config["num_attention_heads"] * 2.0 \
        * config["head_dim"] * scored_windows(config, 0, T, total)
    total_flops += n_light * config["lightning_nh"] * T * 4.0 * d * d
    total_flops += 2.0 * config["vocab_size"] * config["hidden_size"]
    return float(total_flops)


def decode_bytes_per_token(config: dict, prompt_tokens: int,
                           new_tokens: int) -> float:
    """The bytes ONE decoded token must move through HBM, from the sizes in
    the configuration's file: every weight once but the embedding, of which
    one row (``llm.bytes`` holds both tables), every lightning state read
    and written (float32), and per sparse layer and key/value group the
    compressed keys complete at the request's mean decode length and the
    K and V rows of the table's blocks (every row below, for a request
    within ``dense_len``)."""
    D, w = config["hidden_size"], _BYTES[config["llm"]["dtype"]]
    n_light, n_sparse = layer_counts(config)
    d, G = config["head_dim"], config["num_key_value_heads"]
    dl, Hl = config["lightning_head_dim"], config["lightning_nh"]
    total = prompt_tokens + new_tokens
    mean_len = prompt_tokens + new_tokens / 2.0
    weights = config["llm"]["bytes"] - (config["vocab_size"] - 1) * D * w
    states = n_light * 2 * Hl * dl * dl * _BYTES["float32"]
    if selects(config, total):
        table = config["topk"] + config["window_size"] // config["block_size"]
        rows = min(table * config["block_size"], mean_len)
        index = mean_len / config["kernel_stride"] * d * w
    else:
        rows, index = mean_len, 0.0
    return float(weights + states
                 + n_sparse * G * (index + 2 * rows * d * w))


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
    """``sala_share_pct``: seconds inside the language model's two
    programs over the client's wall seconds of the window's requests."""
    if ctx["cell"].config.get("kind") != KIND:
        return None
    done = [r for r in ctx["records"] if r["status"] == "success"]
    inside = moved(ctx, SECONDS, {"pipeline": "^llm_(prefill|decode)$"},
                   "sum")
    if not done or inside == 0.0:
        return None
    return 100.0 * inside / sum(r["seconds"] for r in done)


def decode_ms_per_token(ctx: dict):
    """``sala_decode_ms_per_token``: host seconds inside ``llm_decode``
    over the tokens the cell's graph asks of it."""
    if ctx["cell"].config.get("kind") != KIND or not ctx["requests"]:
        return None
    inside = moved(ctx, SECONDS, {"pipeline": "^llm_decode$"}, "sum")
    if inside == 0.0:
        return None
    return 1000.0 * inside / ctx["requests"] / request_sizes(ctx["cell"])[1]


def prefill_mfu_pct(ctx: dict):
    """``sala_prefill_mfu_pct``: ``prefill_flops`` over the compute peak
    and the traced ``jit_llm_prefill``'s DEVICE time."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    if program is None:
        return None
    prompt_tokens, new_tokens = request_sizes(ctx["cell"])
    need = prefill_flops(ctx["cell"].config, prompt_tokens,
                         prompt_tokens + new_tokens)
    seconds = program["seconds"] / program["count"]
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def decode_hbm_pct(ctx: dict):
    """``sala_decode_hbm_pct``: ``decode_bytes_per_token`` over the HBM
    peak and the traced ``jit_llm_decode``'s DEVICE time a token."""
    program = _traced_program(ctx, "llm_decode")
    if program is None:
        return None
    prompt_tokens, new_tokens = request_sizes(ctx["cell"])
    token_s = program["seconds"] / program["count"] / new_tokens
    need = decode_bytes_per_token(ctx["cell"].config, prompt_tokens,
                                  new_tokens)
    return 100.0 * need / hbm_peak(ctx["device"]["kind"]) / token_s


def sparse_core_mxu_pct(ctx: dict):
    """``sala_sparse_core_mxu_pct``: ``attention_core_flops`` (the
    SELECTED pairs) over the compute peak and the DEVICE seconds under the
    kernel's name in the traced request; None where no such operation
    ran."""
    from cdtbench.flops import peak_flops

    program = _traced_program(ctx, "llm_prefill")
    if program is None:
        return None
    seconds = sum(s for op, s in ctx["trace"]["op_seconds"].items()
                  if re.search(SPARSE_KERNEL, op))
    if not seconds:
        return None
    prompt_tokens, new_tokens = request_sizes(ctx["cell"])
    need = program["count"] * attention_core_flops(
        ctx["cell"].config, prompt_tokens, prompt_tokens + new_tokens)
    return 100.0 * need / peak_flops(ctx["device"]["kind"]) / seconds


def selected_keys_pct(ctx: dict):
    """``sala_selected_keys_pct``: the (query, key) pairs the sparse
    layers' heads attended in the window (the program's counter) over the
    causal pairs of the same queries: 100 the day the layer is served
    dense."""
    cell = ctx["cell"]
    if cell.config.get("kind") != KIND or not ctx["requests"]:
        return None
    seen = moved(ctx, KEYS, {"layers": "^sparse$"})
    if not seen:
        return None
    total = sum(request_sizes(cell))
    causal = layer_counts(cell.config)[1] * total * (total + 1) / 2.0
    return 100.0 * seen / ctx["requests"] / causal


_scope_seconds: dict = {}


def scope_seconds(ctx: dict):
    """DEVICE seconds (self times, mean over the chips) of the traced
    window's operations by the plain named scope of :data:`SCOPES` in their
    name stack, and the window's busy seconds: read from the trace's event
    metadata as ``cdtbench/device_layers.py`` reads the ``cdt.<layer>``
    scopes, once a trace. None without a trace or where no operation
    carries such a scope (the parent; any cell of another kind)."""
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
