"""The ``jamba`` kind: a WHOLE language model of Mamba-1 selective-scan
layers with an attention layer (one shared key/value head) every
fourteenth, a dense FFN on every layer and a tied head, rewriting a very
long prompt in front of a UNET image model. The cell's denoise step is the
image leg's (the configuration's file carries that leg's ``unet``/``vae``
blocks and pinned ``step_flops``), so ``step_call`` is the UNet's; the
language model's own programs are built by ``cdtbench/parity_jamba.py``.
The counts the roofline shares divide by live here, with the benchmark:
``prefill_flops`` (``jamba_prefill_mfu_pct``), ``attention_core_flops``
(``jamba_attn_core_mxu_pct``), ``scan_bytes`` (``jamba_scan_hbm_pct``) and
``decode_bytes_per_token`` (``jamba_decode_hbm_pct``). Each counts what the
program MUST do, whatever implements it."""

from __future__ import annotations

from cdtbench.flops import PEAKS
from cdtbench.kinds import unet
from cdtbench.kinds.llm import request_sizes  # noqa: F401  the rewrite node's, from the graph

F32 = 4
# the names the device trace gives the two kernels' operations (the jitted
# functions around their pallas_calls: ops/flash_latent.py,
# ops/selective_scan.py)
ATTENTION_KERNEL = r"^shared_kv_causal_mha"
SCAN_KERNEL = r"^selective_scan"


def hbm_peak(device_kind: str) -> float:
    """Bytes a second of the device's memory, from ``flops.PEAKS``."""
    for kind, (_, bandwidth, _) in PEAKS.items():
        if kind.lower() in device_kind.lower():
            return bandwidth
    raise ValueError(f"no peak on record for device kind {device_kind!r}: "
                     "add it to cdtbench/flops.py PEAKS with its source")


def step_call(config: dict, lat_h: int, lat_w: int, batch: int):
    return unet.step_call(config, lat_h, lat_w, batch)


def denoise_program(cell, preset, mesh, rep, vae, common):
    raise NotImplementedError(
        "this cell's language programs are llm_prefill and llm_decode: "
        "compile them off-chip with `python -m cdtbench.parity_jamba "
        f"--workload {cell.name} --compile-only` (offchip.py builds image "
        "models; the image leg is sdxl-base's segment program)")


def layer_counts(config: dict) -> tuple[int, int]:
    """``(Mamba layers, attention layers)`` of the depth."""
    attention = sum(
        1 for i in range(config["num_hidden_layers"])
        if i % config["attn_layer_period"] == config["attn_layer_offset"])
    return config["num_hidden_layers"] - attention, attention


def _d_inner(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def _matrix_params(config: dict) -> tuple[int, int, int]:
    """Multiplied weights a token of ``(a Mamba mixer, an attention mixer,
    an FFN)``: norms, the convolution and the scan's own ``A``/``D`` are
    not products."""
    D, Di = config["hidden_size"], _d_inner(config)
    R, N = config["mamba_dt_rank"], config["mamba_d_state"]
    d = D // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * d
    mamba = D * 2 * Di + Di * (R + 2 * N) + R * Di + Di * D
    attention = D * D + 2 * D * kv + D * D
    return mamba, attention, 3 * D * config["intermediate_size"]


def attention_core_flops(config: dict, prompt_tokens: int) -> float:
    """The blocked causal kernel's algorithmic operations in ONE prefill:
    every (query, key) pair with ``key ≤ query`` counted once —
    ``T(T+1)/2`` pairs a head an attention layer — times ``2·d`` for the
    logit and ``2·d`` for the value. A masked half of a diagonal block or
    a skipped block's grid step is the kernel's cost, not its work."""
    pairs = prompt_tokens * (prompt_tokens + 1) / 2.0
    d = config["hidden_size"] // config["num_attention_heads"]
    return float(layer_counts(config)[1] * config["num_attention_heads"]
                 * pairs * 4 * d)


def prefill_flops(config: dict, prompt_tokens: int) -> float:
    """The algorithmic MATRIX operations of ONE ``llm_prefill``: every
    layer's products for every token, the causal core's pairs once, the
    tied head at ONE position. The selective scan's vector work (an
    ``exp`` and ~6 multiply-adds a state update) and the convolution are
    not counted: the compute peak is the matrix unit's."""
    mamba, attention, ffn = _matrix_params(config)
    n_mamba, n_attn = layer_counts(config)
    per_token = n_mamba * (mamba + ffn) + n_attn * (attention + ffn)
    return float(2.0 * prompt_tokens * per_token
                 + attention_core_flops(config, prompt_tokens)
                 + 2.0 * config["vocab_size"] * config["hidden_size"])


def scan_bytes(config: dict) -> float:
    """Bytes ONE token of ONE Mamba layer's selective scan must move: ``u``,
    ``Δ`` and ``z`` read and ``y`` written (``d_inner`` float32 each), ``B``
    and ``C`` read (``d_state`` float32 each). The layer's state (read and
    written once a chunk, 160 bytes a token at 4096) and what an
    implementation re-reads or broadcasts are left out."""
    return float(F32 * (4 * _d_inner(config) + 2 * config["mamba_d_state"]))


def decode_bytes_per_token(config: dict, prompt_tokens: int,
                           new_tokens: int) -> float:
    """The bytes ONE decoded token must move through HBM, from the sizes in
    the configuration's file: every weight once (``llm.bytes``: the tied
    table once, as the head) and one row of it as the embedding, every
    Mamba layer's state and convolution tail read and written (float32),
    and the attention layers' key and value rows at their mean length over
    the request's decode (bfloat16)."""
    Di, D = _d_inner(config), config["hidden_size"]
    n_mamba, n_attn = layer_counts(config)
    d = D // config["num_attention_heads"]
    recurrent = n_mamba * Di * F32 * (config["mamba_d_state"]
                                      + config["mamba_d_conv"] - 1)
    rows = prompt_tokens + new_tokens / 2.0
    return float(config["llm"]["bytes"] + 2 * D + 2 * recurrent
                 + n_attn * rows * 2 * d * 2)
