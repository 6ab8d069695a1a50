"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (guide ``on-chip-measurement`` §2.3). Interpret
mode cannot show what it shows: a kernel that passed every interpret-mode
test was refused on the chip's terms for 4 MB more scoped VMEM than the
repo's own model counted. These cases compile, at real widths:

- every geometry of the model zoo the policy answers ``packed``, alone,
  with its operands as program arguments (the strictest setting the
  compiler has);
- the classic ``bh`` call at FLUX's geometry, which the policy does not
  answer there but the floors of ``select_kernel`` still reach;
- SDXL's two self-attention sites inside the transformer block that calls
  them, with the dispatcher choosing the tier as it does on the chip —
  and, in the same text, where the compiler put the GEGLU's exact gelu:
  in a product's epilogue, not on ``proj_out``'s operand path (PR 35);
- SD3's joint attention (B=2, N=4173, H=24, D=64), inside a label's
  bucket: the packed tier at the blocks its exact shape derives;
- and, for every such geometry and for SD3, the largest blocks the packed VMEM
  model (``_packed_blocks``) approves: a model that says yes where the
  compiler says no is the bug this file exists to catch.

Nothing runs, so nothing here is a result or a time. The persistent
compilation cache is off around the compiles: an entry written for a
described device cannot be read back without one, and only warns.
"""

import importlib.util
import math
import os
import re
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# describing a topology loads libtpu, which one process at a time may do
# unless told otherwise; no chip is opened here, and the suite's workers
# compile side by side
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from comfyui_distributed_tpu.ops import attention as attn
from comfyui_distributed_tpu.ops import flash_attention as fa
from comfyui_distributed_tpu.ops.kernel_choice import GeometryKey

_spec = importlib.util.spec_from_file_location(
    "loop_copies",
    Path(__file__).resolve().parent.parent / "scripts" / "loop_copies.py")
loop_copies = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(loop_copies)

# the model zoo's serving geometries (docs/roofline.md, r05's table):
# (heads, head_dim, q_len, kv_len)
ZOO = {
    # SDXL UNet at 1024²: 64² = 4096 tokens at 10 heads × 64, 32² = 1024
    # tokens at 20 × 64, and their 77-token cross-attention contexts
    "sdxl_self64": (10, 64, 4096, 4096),
    "sdxl_self32": (20, 64, 1024, 1024),
    "sdxl_cross64": (10, 64, 4096, 77),
    "sdxl_cross32": (20, 64, 1024, 77),
    # FLUX-12B at 1024²: 4096 image + 512 text joint tokens, 24 × 128
    "flux_joint": (24, 128, 4608, 4608),
    # WAN-1.3B t2v 33 frames at 480p: 14 040 tokens, 12 × 128, and the
    # 512-token text cross-attention
    "wan_self": (12, 128, 14040, 14040),
    "wan_cross": (12, 128, 14040, 512),
}
# those the one policy answers with a Pallas tier, by geometry label
PALLAS_ROWS = {
    key.key_str(): (key, choice)
    for key, choice in ((GeometryKey.from_shape(H, D, nq, nk),
                         attn.policy_choice(nq, nk, H, D))
                        for H, D, nq, nk in ZOO.values())
    if choice.tier != "xla"}


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip of a 2x2 host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_kernel(chip, tier, H, D, nq, nk, bq, bk, batch=1):
    """Compile one kernel call alone, operands as arguments; raises what
    the chip's compiler raises."""
    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    if tier == "packed":
        q, kv = arg(batch, nq, H * D), arg(batch, nk, H * D)
        lowered = fa._flash_mha_packed.lower(
            q, kv, kv, num_heads=H, block_q=bq, block_k=bk,
            interpret=False)
    else:
        q, kv = arg(batch * H, nq, D), arg(batch * H, nk, D)
        lowered = fa._flash_mha.lower(q, kv, kv, block_q=bq, block_k=bk,
                                      interpret=False)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


# (id, tier, H, D, Nq, Nk, block_q, block_k, batch): the zoo's Pallas
# geometries at their label's bucket lengths (a packed choice takes the
# shape's blocks, as the dispatcher resolves it), WAN also at its real 14 040
# tokens (not a block multiple: the call pads) with the shape's blocks and
# with requested 256/512 streaming K, SD3's joint attention at the CFG
# batch, the classic call the policy never picks there, and SDXL's 64² site at the
# CFG batch (as it runs)
KERNEL_CASES = [
    (ks, c.tier, k.num_heads, k.head_dim, k.q_bucket, k.kv_bucket,
     c.block_q, c.block_k, 1)
    for ks, (k, c) in sorted(PALLAS_ROWS.items())
] + [
    ("wan_self_14040", "packed", 12, 128, 14040, 14040, None, None, 1),
    ("wan_self_14040_streamed", "packed", 12, 128, 14040, 14040, 256, 512, 1),
    ("wan_self_32760", "packed", 12, 128, 32760, 32760, None, None, 1),
    ("sd3_joint_4173", "packed", 24, 64, 4173, 4173, None, None, 2),
    ("sd3_joint_4173_streamed", "packed", 24, 64, 4173, 4173, 256, 512, 2),
    ("flux_bh_h24.d128.q8192", "bh", 24, 128, 8192, 8192, 256, 512, 1),
    ("sdxl_self64_cfg", "packed", 10, 64, 4096, 4096, None, None, 2),
]


@pytest.mark.parametrize("case", KERNEL_CASES, ids=lambda c: c[0])
def test_table_row_compiles_alone(chip, case):
    _, tier, H, D, nq, nk, bq, bk, batch = case
    if tier == "packed":
        bq, bk = fa._packed_blocks(nq, nk, D, 2, bq, bk)
    _compile_kernel(chip, tier, H, D, nq, nk, bq, bk, batch=batch)


def test_table_has_the_rows_the_main_paths_select():
    """The cases above are what the policy answers over the zoo; a policy
    that stopped answering ``packed`` must not pass by compiling nothing."""
    assert sorted(PALLAS_ROWS) == [
        "h10.d64.q4096.kv4096.bf16", "h12.d128.q16384.kv16384.bf16",
        "h12.d128.q16384.kv512.bf16", "h20.d64.q1024.kv1024.bf16",
        "h24.d128.q8192.kv8192.bf16"]
    assert {c.tier for _, c in PALLAS_ROWS.values()} == {"packed"}


_FF_ERFC = re.compile(r'op_name="[^"]*/ff/[^"]*erfc')
_computations = loop_copies.computations


def _pallas_calls(text, jitted=None):
    """The Pallas call lines of a compiled module's text (those traced
    under ``jit(<jitted>)`` where a name is given): each carries its
    operands' shapes as ``operand_layout_constraints``."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and (jitted is None or f"jit({jitted})/pallas_call" in line)]


def _copy_sizes(text, looped):
    """Bytes of each copy of 1 MiB or more a compiled module holds inside
    its while bodies — paid at every trip — or (``looped`` false) outside
    them, as ``scripts/loop_copies.py`` lists them."""
    return [size for _, where, size, _ in loop_copies.large_copies(text)
            if where == looped]


def _assert_gelu_is_a_products_epilogue(text):
    """The exact gelu is 64 vector instructions an element. Every one of
    the feed-forward's must sit in a computation that holds a
    ``convolution`` (the epilogue of that product's own output fusion,
    beside the matrix unit), none in a loop fusion that prepares another
    product's operand, where the matrix unit waits for it."""
    holders = {name: body for name, body in _computations(text).items()
               if any(_FF_ERFC.search(line) for line in body)}
    assert holders, "no ff/…erfc instruction: the reader is looking wrong"
    for name, body in holders.items():
        assert any(" convolution(" in line for line in body), (
            f"{name} computes the feed-forward's erfc and holds no "
            "convolution")
    products = re.findall(
        r' convolution\(.*op_name="[^"]*/ff/cdt\.ffn/(\w+)/', text)
    assert sorted(products) == ["gate", "proj_out", "value"], products
    assert not re.findall(r"= \S+ copy\(.*proj_in", text)


@pytest.mark.parametrize("level", [(640, 10, 4096), (1280, 20, 1024)],
                         ids=lambda l: f"c{l[0]}.n{l[2]}")
def test_sdxl_self_attention_inside_its_block(chip, level, monkeypatch):
    """One SDXL transformer block (self-attention, cross-attention to 77
    text tokens, GEGLU) at the width of the 64² and the 32² level, CFG
    batch 2, with the dispatcher choosing as it does on the chip: the
    Pallas call compiles where other ops of the same program produce its
    operands, and the tier is the policy's."""
    from comfyui_distributed_tpu.models.layers import TransformerBlock

    C, heads, n = level
    monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
    # the one place the kernels ask where they are (ops/flash_attention):
    # steered here, in the test, since jax.devices() still says cpu
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    attn.reset_selections()

    block = TransformerBlock(heads, C // heads)
    x = jax.ShapeDtypeStruct((2, n, C), jnp.bfloat16, sharding=chip)
    ctx = jax.ShapeDtypeStruct((2, 77, 2048), jnp.bfloat16, sharding=chip)
    params = jax.eval_shape(
        lambda: block.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype),
                           jnp.zeros(ctx.shape, ctx.dtype)))
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16, sharding=chip),
        params)
    compiled = jax.jit(block.apply).lower(params, x, ctx).compile()

    text = compiled.as_text()
    assert "tpu_custom_call" in text
    _assert_gelu_is_a_products_epilogue(text)
    selected = dict(item.split("=") for item in
                    attn.selection_summary().split(","))
    key = GeometryKey.from_shape(heads, C // heads, n, n).key_str()
    assert selected[key].startswith("packed"), selected
    cross = GeometryKey.from_shape(heads, C // heads, n, 77)
    assert selected[cross.key_str()] == "xla", selected


def _packed_frontier(nq, nk, D):
    """The packed VMEM model's largest approvals at one geometry: the
    tallest doubling q block it still holds the whole sequence against
    (K resident) and the first past it, where K must stream — the two
    working sets nearest the budget."""
    resident = streamed = None
    bq = 512
    while bq <= 8192 and streamed is None:
        try:
            blocks = fa._packed_blocks(nq, nk, D, 2, bq, None)
        except ValueError:
            break
        if blocks[1] >= nk:
            resident = blocks
        else:
            streamed = blocks
        bq *= 2
    return [b for b in (resident, streamed) if b]


PACKED_GEOMETRIES = {
    **{ks: (k.num_heads, k.head_dim, k.q_bucket, k.kv_bucket)
       for ks, (k, _) in PALLAS_ROWS.items()},
    "sd3_joint_4173": (24, 64, 4173, 4173),
    "wan_self_32760": (12, 128, 32760, 32760),
}


@pytest.mark.parametrize("row", sorted(PACKED_GEOMETRIES), ids=str)
def test_feasibility_never_approves_what_the_compiler_refuses(chip, row):
    """The VMEM model against the compiler, on the zoo's packed geometries
    and on SD3's joint site: whatever the packed model approves for the
    geometry — not only the pair the shape chose — must compile."""
    H, D, nq, nk = PACKED_GEOMETRIES[row]
    approved = _packed_frontier(nq, nk, D)
    assert approved, f"nothing approved for {row}"
    for bq, bk in approved:
        try:
            _compile_kernel(chip, "packed", H, D, nq, nk, bq, bk)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            pytest.fail(f"packed {bq}/{bk} approved for {row} but "
                        f"refused by the compiler: {str(e)[:400]}")


def test_the_latent_causal_kernel_compiles_at_the_served_geometry(chip):
    """``ops/flash_latent.py`` as the long-brief rewriter's prefill calls
    it: 64 heads of 128 + the shared 64-wide rope key, 128-wide values, a
    4096-token chunk over a 32 k workspace, the tile the config ships, the
    chunk's start a traced scalar (one compiled kernel serves every
    chunk)."""
    from comfyui_distributed_tpu.models.llm_kimi import KimiConfig
    from comfyui_distributed_tpu.ops import flash_latent

    cfg = KimiConfig.kimi_share()
    H, C, S = cfg.num_attention_heads, cfg.prefill_chunk_tokens, 36864

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    lowered = flash_latent.latent_causal_mha.lower(
        arg(C, H * cfg.qk_nope_head_dim), arg(H, C, cfg.qk_rope_head_dim),
        arg(S, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        arg(S, cfg.qk_rope_head_dim),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=chip), num_heads=H,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k, interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_the_selective_scan_kernel_compiles_at_the_served_geometry(chip):
    """``ops/selective_scan.py`` as the state-space rewriter's prefill calls
    it: a 4096-token chunk of 5120 channels and 16 states, the blocks the
    module ships, the gate handed over as the ``[u | z]`` product whole
    (``f32[4096, 10240]``) — and the Pallas call takes it as it is (PR 46)."""
    from comfyui_distributed_tpu.ops import selective_scan as ss

    T, d, N = 4096, 5120, 16

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    lowered = ss.selective_scan.lower(
        arg(d, N), arg(T, d), arg(T, d), arg(T, 2 * d), arg(T, N), arg(T, N),
        arg(d, N), arg(d), block_t=ss.BLOCK_T, block_d=ss.BLOCK_D,
        unroll=ss.UNROLL)
    text = lowered.compile().as_text()
    call, = _pallas_calls(text, "selective_scan")
    assert "f32[4096,10240]{1,0}" in call
    assert not [move for move in loop_copies.large_moves(text)
                if move[0] == "slice"]


def test_the_convolution_kernel_compiles_at_the_served_geometry(chip):
    """``selective_scan.causal_conv_silu`` as the same prefill calls it: the
    four taps and the silu over the left half of the ``[u | z]`` product,
    handed over whole, in the blocks the module ships."""
    from comfyui_distributed_tpu.ops import selective_scan as ss

    T, d, K = 4096, 5120, 4

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    text = ss.causal_conv_silu.lower(
        arg(K - 1, d), arg(T, 2 * d), arg(K, d), arg(d),
        block_t=ss.CONV_BLOCK_T, block_d=ss.CONV_BLOCK_D).compile().as_text()
    call, = _pallas_calls(text, "causal_conv_silu")
    assert "f32[4096,10240]{1,0}" in call
    assert not [move for move in loop_copies.large_moves(text)
                if move[0] == "slice"]


def test_the_shared_kv_causal_kernel_compiles_at_the_served_geometry(chip):
    """``flash_latent.shared_kv_causal_mha`` — the grouped-query body under
    its third name — as the same prefill calls it: 20 query heads of 128
    over one key/value head, a 4096-token chunk over the 64 k cache padded
    to the K block (67 584 rows), the tile the config ships (2048 × 2048), a
    traced start. The compiled kernel carries the NAME: what the cell's
    trace readers match (``cdtbench/kinds/jamba.py``)."""
    from comfyui_distributed_tpu.models.llm_jamba import JambaConfig
    from comfyui_distributed_tpu.ops import flash_latent

    cfg = JambaConfig.jamba2_3b()
    H, d, C = cfg.num_attention_heads, cfg.head_dim, 4096
    S = -(-(65536 + 128) // cfg.attn_block_k) * cfg.attn_block_k

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    assert (H, d, S) == (20, 128, 67584)
    assert (cfg.attn_block_q, cfg.attn_block_k) == (2048, 2048)
    lowered = flash_latent.shared_kv_causal_mha.lower(
        arg(C, H * d), arg(S, d), arg(S, d),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=chip), num_heads=H,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k, interpret=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "shared_kv_causal_mha" in text


def test_the_state_space_rewriters_programs_fit_beside_sdxl(chip,
                                                            monkeypatch):
    """Both language programs of ``ai21-jamba2-3b.brief64k-sdxl8`` at the
    cell's sizes (65 536 + 128 tokens, the WHOLE model): they compile for
    the chip, their arguments + temporaries leave room for SDXL's segment
    program (4.79 + 0.56 GiB, docs/weights.md) in 15.75 GiB, and the
    program holds two Pallas call sites a run of Mamba layers (the
    convolution and the scan) and one an attention layer — eight — not two
    a layer (54+), so a warm set-up re-lowers eight kernels (PERF.md §2)."""
    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.models.llm_jamba import JambaConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = JambaConfig.jamba2_3b()

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    weights = place(cfg.model.init(cfg, None, abstract=True))
    prefill, decode = LLMPipeline(cfg, weights).programs(65536, 128)
    ids = jax.ShapeDtypeStruct((65536,), jnp.int32, sharding=chip)
    logits, cache, *_ = jax.eval_shape(prefill.jitted, weights, ids)
    key = jax.eval_shape(lambda: jax.random.key(0))
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    compiled = prefill.jitted.lower(weights, ids).compile()
    assert len(_pallas_calls(compiled.as_text())) \
        == 2 * len(cfg.mamba_runs) + len(cfg.attention_layers) == 8
    mem = compiled.memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 5.6 < prefill_gib < 7.0 and prefill_gib + sdxl < 15.75 - 2.0
    compiled = decode.jitted.lower(
        weights, place(logits), place(cache), place(key),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()     # decode is XLA
    mem = compiled.memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 5.6 < decode_gib < 6.5 and decode_gib + sdxl < 15.75 - 2.0


def test_a_mamba_layer_hands_both_kernels_uz_where_w_in_wrote_it(
        chip, monkeypatch):
    """``llm_prefill`` of the state-space rewriter at its cell's 65 536
    tokens: in every run of Mamba layers the ``[u | z]`` product's
    ``f32[4096,10240]`` output reaches BOTH Pallas calls whole — the
    convolution reads its left half, the selective scan its right — and
    nothing, on the core or as an asynchronous ``slice-start``, alone or in
    a fusion of nothing but slices, copies either half. Until PR 46 a
    two-way slice fusion read the product's 168 MB and wrote both halves a
    layer a chunk, 416 times a request (0.153 s), because the kernel was
    handed ``uz[:, Di:]`` and an operand of a Pallas call is an array of its
    own. Nor does XLA broadcast ``B`` / ``C`` to ``[4096,16,128]`` in HBM
    any more (two 33.6 MB arrays a layer a chunk): the scan kernel spreads
    them along the lanes in VMEM (PERF.md §6, PR 46)."""
    from comfyui_distributed_tpu.models.registry import PRESETS

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = PRESETS["ai21-jamba2-3b"].llm
    text = loop_copies.compiled_programs(
        cfg, 65536, 128, chip, ["llm_prefill"])["llm_prefill"].as_text()
    runs = sum(1 for n in cfg.mamba_runs if n)
    for name in ("causal_conv_silu", "selective_scan"):
        calls = _pallas_calls(text, name)
        assert len(calls) == runs == 3
        assert all("f32[4096,10240]{1,0}" in call for call in calls)
    moves = loop_copies.large_moves(text, 32 * 2 ** 20)
    assert not [source for kind, *_, source in moves
                if kind == "slice" and source.startswith("f32[4096,10240]")]
    assert not [shape for kind, _, looped, _, shape, _ in moves
                if kind == "broadcast" and looped]


@pytest.mark.parametrize("window", [None, 4096])
def test_the_grouped_query_kernel_compiles_at_the_served_geometry(chip,
                                                                  window):
    """``flash_latent``'s third kernel under both of its names as the
    window/full rewriter's prefill calls it: 48 query heads of 128 over 8
    key/value heads, a 4096-token chunk — over the 128 k buffer rounded to
    the K block (``gqa_causal_mha``) and over ``[ring ; chunk]`` under a
    band of 4096 (``gqa_window_mha``) — each at the tile IT ships with
    (the full layer's is the larger: where a tile outgrows VMEM, this is
    where it fails first), traced bounds."""
    from comfyui_distributed_tpu.models import llm_trinity
    from comfyui_distributed_tpu.ops import flash_latent

    cfg = llm_trinity.TrinityConfig.trinity_share()
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    C = cfg.prefill_chunk_tokens
    buffer = jax.eval_shape(lambda: llm_trinity.empty_cache(
        cfg, 131072 + 128))["k"][cfg.layer_types.index(llm_trinity.FULL)]
    S = buffer.shape[1] if window is None else 2 * window
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    bq, bk = (cfg.attn_full_block_q, cfg.attn_full_block_k) \
        if window is None \
        else (cfg.attn_window_block_q, cfg.attn_window_block_k)
    assert S % bk == 0 and C % bq == 0
    blocks = dict(num_heads=H, block_q=bq, block_k=bk, interpret=False)
    if window is None:
        lowered = flash_latent.gqa_causal_mha.lower(
            arg(C, H * d), arg(G, S, d), arg(G, S, d), scalar, **blocks)
    else:
        lowered = flash_latent.gqa_window_mha.lower(
            arg(C, H * d), arg(G, S, d), arg(G, S, d), scalar, scalar,
            window=window, **blocks)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("cell", ["zaya1-8b", "trinity-large-preview"])
def test_the_parted_step_compiles_under_the_kernels_own_vmem_limit(chip,
                                                                  cell):
    """PR 63's form of the grouped-query body as the two long-context
    cells serve it: a 2048 × 2048 tile whose step takes the query tile in
    the parts ``step_rows`` says (two float32 logit parts of ``[part, 2048]``
    alive at once, the next one's product ahead of a part's softmax) over a
    grid whose K extent is TRACED (``core_k_steps``), under the 64 MiB the
    kernel asks for itself — 8 q / 2 kv heads over 131 072 rows and 48 / 8
    over 133 120."""
    from comfyui_distributed_tpu.models import llm_trinity, llm_zaya
    from comfyui_distributed_tpu.ops import flash_latent

    if cell == "zaya1-8b":
        cfg = llm_zaya.ZayaConfig.zaya_share()
        bq, bk = cfg.attn_block_q, cfg.attn_block_k
        S = jax.eval_shape(lambda: llm_zaya.empty_cache(
            cfg, 130944 + 128))["k"][0].shape[1]
    else:
        cfg = llm_trinity.TrinityConfig.trinity_share()
        bq, bk = cfg.attn_full_block_q, cfg.attn_full_block_k
        S = -(-(131072 + 128) // bk) * bk
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    C, part = cfg.prefill_chunk_tokens, flash_latent.step_rows(bq)
    assert (bq, bk, part) == (2048, 2048, flash_latent.STEP_ROWS)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def served(q, k, v, start):
        steps = flash_latent.core_k_steps(start, C, bk, S // bk)
        return flash_latent.gqa_call(q, k, v, start, 0, steps, H, None, bq,
                                     bk, part, False)

    lowered = jax.jit(served).lower(arg((C, H * d)), arg((G, S, d)),
                                    arg((G, S, d)), arg((), jnp.int32))
    assert str(flash_latent._VMEM_LIMIT_BYTES) in lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and f"bf16[{C},{H * d}]" in text


def test_the_window_and_full_rewriters_programs_fit_beside_sdxl(chip,
                                                                monkeypatch):
    """Both language programs of ``trinity-large-preview.brief128k-sdxl8``
    at the cell's sizes (131 072 + 128 tokens, the published widths, 16
    experts held): they compile for the chip, their arguments + temporaries
    leave room for SDXL's segment program (4.79 + 0.56 GiB,
    docs/weights.md) in 15.75 GiB, ``llm_prefill`` holds one Pallas call
    site a layer — five — and updates the full layer's buffer in place."""
    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = TrinityConfig.trinity_share()

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    weights = place(cfg.model.init(cfg, None, abstract=True))
    prefill, decode = LLMPipeline(cfg, weights).programs(131072, 128)
    ids = jax.ShapeDtypeStruct((131072,), jnp.int32, sharding=chip)
    logits, cache, *_ = jax.eval_shape(prefill.jitted, weights, ids)
    key = jax.eval_shape(lambda: jax.random.key(0))
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    compiled = prefill.jitted.lower(weights, ids).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        == cfg.num_hidden_layers == 5
    # the 0.25 GiB K and V buffers are written where they lie: no copy of
    # one inside the scan of chunks
    full = cache["k"][cfg.layer_types.index("full_attention")]
    buffer = "bf16[{},{},{}]".format(*full.shape)
    assert full.shape[1] % cfg.attn_full_block_k == 0
    assert not [line for line in text.splitlines()
                if buffer in line.split("=")[0] and " copy(" in line]
    mem = compiled.memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 4.9 < prefill_gib < 6.2 and prefill_gib + sdxl < 15.75 - 2.0
    compiled = decode.jitted.lower(
        weights, place(logits), place(cache), place(key),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()     # decode is XLA
    mem = compiled.memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 5.2 < decode_gib < 6.8 and decode_gib + sdxl < 15.75 - 2.0


def test_the_double_layer_rewriters_programs_fit_beside_sdxl(chip,
                                                             monkeypatch):
    """Both language programs of ``longcat-flash-omni.brief16k-sdxl8`` at
    the cell's sizes (16 384 + 256 tokens, the published widths, 8 experts
    held): they compile for the chip, their arguments + temporaries leave
    room for SDXL's segment program's weights (4.79 GiB, docs/weights.md)
    in 15.75 GiB, ``llm_prefill`` holds one call site of the causal latent
    kernel an attention SUBLAYER — eight, what Kimi's form needs a layer
    and no more — and writes the sixteen latent leaves where they lie: no
    copy of one inside the scan of chunks. ``W_b`` (16.8 MB a sublayer) is
    copied nowhere in ``llm_prefill`` and once a sublayer AHEAD of
    ``llm_decode``'s token loop, whose body copies nothing of 1 MiB."""
    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.models.llm_longcat import LongcatConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = LongcatConfig.longcat_share()

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    weights = place(cfg.model.init(cfg, None, abstract=True))
    prefill, decode = LLMPipeline(cfg, weights).programs(16384, 256)
    ids = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=chip)
    logits, cache, counts, rows = jax.eval_shape(prefill.jitted, weights,
                                                 ids)
    assert counts.shape == (2 * cfg.num_layers,)      # [held … | zero …]
    assert rows.shape == (cfg.num_layers,)
    assert len(cache["c"]) == len(cache["kr"]) == 2 * cfg.num_layers == 8
    key = jax.eval_shape(lambda: jax.random.key(0))
    gib, sdxl = 2.0 ** 30, 4.79
    compiled = prefill.jitted.lower(weights, ids).compile()
    text = compiled.as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") \
        == 2 * cfg.num_layers == 8
    assert "latent_causal_mha" in text
    for leaf in (cache["c"][0], cache["kr"][0]):
        buffer = "bf16[{},{}]".format(*leaf.shape)
        assert leaf.shape[0] == 16640
        assert not [line for line in text.splitlines()
                    if buffer in line.split("=")[0] and " copy(" in line]
    mem = compiled.memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    assert 8.5 < prefill_gib < 9.6 and prefill_gib + sdxl < 15.75 - 1.0
    w_b = weights["layers"][0]["sub"][0]["attn"]["w_b"]
    w_b_bytes = w_b.size * w_b.dtype.itemsize
    assert w_b_bytes == 512 * 16384 * 2
    assert w_b_bytes not in _copy_sizes(text, True) + _copy_sizes(text,
                                                                  False)
    compiled = decode.jitted.lower(
        weights, place(logits), place(cache), place(key),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text                   # decode is XLA
    assert not _copy_sizes(text, True)
    assert _copy_sizes(text, False).count(w_b_bytes) == 2 * cfg.num_layers
    mem = compiled.memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 7.5 < decode_gib < 8.4 and decode_gib + sdxl + 0.56 < 15.75 - 1.0


# (preset, prompt + new tokens of its cell, copies of W_b ahead of the token
# loop, copies of it inside)
LATENT_DECODERS = [("kimi-k2.6", 32768, 128, 5, 0),
                   ("ling-3.0-flash-vl", 512, 1024, 0, 1)]


@pytest.mark.parametrize("case", LATENT_DECODERS, ids=lambda c: c[0])
def test_a_latent_decoders_token_loop_copies_no_matrix_through_hbm(
        chip, case, monkeypatch):
    """``llm_decode`` of the other two rewriters that decode through
    ``mla_absorbed_step``, alone, at their cells' sizes. Kimi's model gives
    ``decode_weights``: the re-tiling of its five ``W_b`` stands in the
    entry computation, once a request, and the while body copies nothing
    of 1 MiB (until PR 45: five 16.8 MB copies a token). Ling's does not:
    its one 8.4 MB copy stays in the loop because its RESULT lies in VMEM
    (``S(1)``) — the copy is the product's operand fetch, the matrix is
    read from HBM once a token — and the form made ahead of the loop read
    1.8345 ms a token against 1.8127 on the chip (PERF.md §6, PR 45). If
    that copy ever lands in HBM, measure again."""
    from comfyui_distributed_tpu.models.registry import PRESETS

    preset, prompt_tokens, new_tokens, ahead, inside = case
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = PRESETS[preset].llm
    compiled = loop_copies.compiled_programs(
        cfg, prompt_tokens, new_tokens, chip, ["llm_decode"])["llm_decode"]
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    w_b_bytes = 2 * cfg.kv_lora_rank * cfg.num_attention_heads \
        * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    assert _copy_sizes(text, True) == [w_b_bytes] * inside
    assert all("S(1)" in shape for _, looped, _, shape
               in loop_copies.large_copies(text) if looped)
    assert _copy_sizes(text, False).count(w_b_bytes) == ahead


# (id, config, text rows, batch, label): a joint block as its model's cell
# or card runs it, 1024² (4096 image rows)
JOINT_BLOCKS = [
    ("sd3_medium", "sd3_medium", 77, 2, "packed:512+80/4096+128:k-resident"),
    ("flux", "flux", 512, 1, "packed:512+512/4096+512:k-resident"),
]


@pytest.mark.parametrize("case", JOINT_BLOCKS, ids=lambda c: c[0])
def test_a_joint_block_hands_the_kernel_its_products_outputs(chip, case,
                                                             monkeypatch):
    """One MMDiT joint block at published widths with the dispatcher
    choosing as it does on the chip (PR 41): ONE Pallas call site, reported
    with tiles a segment, and no operation anywhere in the compiled block
    that holds the joint rows — no concatenated q, k or v (4173 rows), no
    padded copy (4176, 4224), no joint answer to cut. SD3's kernel reads
    the image ``qkv`` product's own output three times (and the text
    product's zero-padded tile three times); where a model ropes and norms
    q and k (FLUX) it still reads v there."""
    from comfyui_distributed_tpu.models import dit

    _, preset, T, B, label = case
    monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    attn.reset_selections()
    cfg = getattr(dit.DiTConfig, preset)()
    N, hd = 4096, cfg.head_dim
    block = dit.DoubleBlock(cfg)

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    args = [arg(B, N, cfg.hidden), arg(B, T, cfg.hidden), arg(B, cfg.hidden)]
    if cfg.pos_embed == "rope":
        args += [(arg(n, hd // 2, dtype=jnp.float32),) * 2 for n in (N, T)]

    def forward(params, img, txt, vec, *pe):
        return block.apply(params, img, txt, vec, None, *pe)

    zeros = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   args)
    params = jax.eval_shape(
        lambda: block.init(jax.random.key(0), *zeros[:3], None, *zeros[3:]))
    params = jax.tree_util.tree_map(
        lambda p: arg(*p.shape), params)
    text = jax.jit(forward).lower(params, *args).compile().as_text()

    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, len(calls)
    assert "_flash_mha_packed_joint" in calls[0]
    joint_rows = re.findall(
        rf"\[{B},(?:{T + N}|{-(-(T + N) // 16) * 16}|"
        rf"{-(-(T + N) // 128) * 128}),\d+", text)
    assert not joint_rows, joint_rows[:3]
    # text q, image q, text k, image k, text v, image v
    operands = re.sub(r"/\*[^*]*\*/", "", re.search(
        r"custom-call\(([^)]*)\)", calls[0]).group(1)).split(", ")
    image_v = next(line for line in text.splitlines()
                   if line.strip().startswith(operands[5] + " = "))
    assert re.search(r'op_name="[^"]*img_qkv/[^"]*dot_general', image_v), \
        image_v[:300]
    if not (cfg.qk_norm or cfg.pos_embed == "rope"):
        assert operands[1] == operands[3] == operands[5], operands
        assert operands[0] == operands[2] == operands[4], operands
    key = GeometryKey.from_shape(cfg.heads, hd, T + N, T + N)
    assert attn.selection_summary() == f"{key.key_str()}={label}"


def test_the_table_driven_kernel_compiles_at_the_served_geometry(chip):
    """``block_select_attention.block_select_mha`` as the sparse layers'
    prefill calls it: 512 queries a call in tiles of 64 neighbours × 16
    heads of a K/V group (1024 rows), the K and V rows of a grid step — 16
    blocks of 64 — brought from the 65 664-row buffers (left in HBM) by
    the kernel's OWN copies into two 1024-row buffers each, ONE stretch
    where the PREFETCHED union table's entries are consecutive and a copy
    a block where not (PR 50: no 32 BlockSpecs a step); the table has an
    entry for every block the compressed cache has slots for (1056: 66
    steps a tile). The compiled kernel carries the NAME the cell's trace
    readers match (``cdtbench/kinds/sala.py``)."""
    from comfyui_distributed_tpu.models.llm_sala import SalaConfig
    from comfyui_distributed_tpu.ops import block_select_attention as bsa

    cfg = SalaConfig.sala_cut()
    G, d = cfg.num_key_value_heads, cfg.head_dim
    J = cfg.num_attention_heads // G
    bq, R, bs = cfg.sparse_block_q, cfg.sparse_blocks_per_step, cfg.block_size
    S = cfg.cache_rows(65536 + 128)
    tiles = cfg.select_rows // bq
    U = -(-(cfg.cache_slots(S) // cfg.selection.per) // R) * R
    assert (S, tiles, U, J * bq) == (65664, 8, 1056, 1024)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    lowered = bsa.block_select_mha.lower(
        arg((G, tiles, J * bq, d)), arg((G, S, d)), arg((G, S, d)),
        arg((G, tiles, U), jnp.int32), arg((G, tiles), jnp.int32),
        arg((G, tiles, U // R, bq, R), jnp.float32), arg((), jnp.int32),
        block=bs, interpret=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "block_select_mha" in text
    # one call, five operands past the three prefetched tables: the
    # queries, the mask and K and V WHOLE (no block of them a BlockSpec)
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and " custom-call(" in line)
    assert call.count(f"bf16[{G},{S},{d}]") == 2
    assert len(re.findall(r"%\S+", call.split(" custom-call(")[1]
                          .split(")")[0])) == 7


def test_the_scoring_kernel_compiles_at_the_served_geometry(chip):
    """``block_select_attention.block_score_sums`` as the sparse layers'
    prefill calls it (PR 48): 512 neighbouring queries a call in tiles of
    128 × 16 heads of a K/V group (2048 rows), the 4224 compressed slots in
    three tiles of 1408 whole lanes, two halves — a float32 ``[2048,
    1408]`` logit tile and its temporaries in VMEM. Its name is NOT the
    sparse kernel's (``cdtbench/kinds/sala.py: SPARSE_KERNEL`` reads that
    one alone)."""
    from comfyui_distributed_tpu.models.llm_sala import SalaConfig
    from comfyui_distributed_tpu.ops import block_select_attention as bsa

    cfg = SalaConfig.sala_cut()
    G, d = cfg.num_key_value_heads, cfg.head_dim
    J = cfg.num_attention_heads // G
    Sc = cfg.cache_slots(cfg.cache_rows(65536 + 128))
    bq, slots = bsa.score_tiles(cfg.select_rows, Sc)
    assert (Sc, bq, slots, J * bq) == (4224, 128, 1408, 2048)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    lowered = bsa.block_score_sums.lower(
        arg((G, cfg.select_rows // bq, J * bq, d)), arg((G, Sc, d)),
        arg((), jnp.int32), block_q=bq, block_slots=slots,
        stride=cfg.kernel_stride, interpret=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "block_score_sums" in text
    assert not re.search(r"(?m)^\s*%?block_select_mha", text)
    assert f"f32[{G},{cfg.select_rows},{Sc}]" in text       # the group sums


def test_the_selecting_rewriters_programs_fit_beside_sdxl(chip, monkeypatch):
    """Both language programs of ``minicpm-sala.brief64k-sdxl8`` at the
    cell's sizes (65 536 + 128 tokens, published widths, 12 layers): they
    compile for the chip and leave room for SDXL's segment program (4.79 +
    0.56 GiB) in 15.75 GiB; the prefill holds TWO Pallas call sites a
    sparse layer — the scoring kernel, then the table-driven one —; the
    largest float32 buffer with a ``slots`` axis is the GROUP sums ``[2,
    512, 4224]`` (PR 48: no per-head ``[2, 8192, 4224]`` scores are
    written, and never a whole chunk's ``[4096, 32 heads, 4224 slots]``,
    2.1 GiB: the queries score 512 at a time, the slots in whole lanes:
    ``cache_slots``); and ``llm_decode`` holds no Pallas call — one token
    scores in the plain form — and its token loop copies nothing of 1 MiB
    (the states are a leaf a layer: stacked, all nine were copied a
    token)."""
    from comfyui_distributed_tpu.models.llm_sala import SalaConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = SalaConfig.sala_cut()
    compiled = loop_copies.compiled_programs(cfg, 65536, 128, chip)
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    text = compiled["llm_prefill"].as_text()
    calls = _pallas_calls(text)
    assert len(calls) == 2 * len(cfg.sparse_layers) == 6
    assert sum("block_score_sums" in c for c in calls) \
        == sum("block_select_mha" in c for c in calls) == 3
    slots = cfg.cache_slots(cfg.cache_rows(65536 + 128))
    scores = [math.prod(int(n) for n in shape.split(","))
              for shape in re.findall(r"f32\[([\d,]+)\]", text)
              if str(slots) in shape.split(",")]
    assert slots == 4224 and scores
    assert max(scores) == 2 * cfg.select_rows * slots
    # the kernel takes the K and V buffers WHOLE and where they lie (PR
    # 50): nothing of their size is copied at a trip of the chunks' scan,
    # and the loops copy what they did before it (92 copies, 2737 MB: the
    # lightning layers' blocks, the tables, the masks)
    looped = [(size, shape) for _, where, size, shape
              in loop_copies.large_copies(text) if where]
    rows = cfg.cache_rows(65536 + 128)
    assert not [shape for _, shape in looped if str(rows) in shape]
    assert sum(size for size, _ in looped) <= 2737e6
    mem = compiled["llm_prefill"].memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    assert 8.0 < prefill_gib < 8.4 and prefill_gib + sdxl < 15.75 - 1.0
    text = compiled["llm_decode"].as_text()
    assert "tpu_custom_call" not in text                   # decode is XLA
    assert not _copy_sizes(text, True)
    mem = compiled["llm_decode"].memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 7.5 < decode_gib < 8.4 and decode_gib + sdxl < 15.75 - 1.0


GLM_KERNELS = ("index_score_sums", "index_select_keep", "index_masked_mha",
               "index_fill_kv")


@pytest.mark.parametrize("kernel", GLM_KERNELS)
def test_the_index_selecting_kernels_compile_at_the_served_geometry(chip,
                                                                   kernel):
    """``ops/index_select_attention``'s kernels as ``glm-5``'s prefill
    calls them (PR 51), alone: the scores of 1024 queries × 32 index heads
    of 128 against 69 632 index keys; the exact top 2048 of 1024 rows, 64
    rows' float32 scores (17 MiB, twice buffered) and their int32 order
    image in VMEM for all the passes, searched in the 4096-column tiles a
    step's rows see (PR 54: a loop whose length the position decides, the
    tiles at lane offsets the compiler must take as aligned); 4096 queries
    of 8 heads × 256/256 over a decompressed workspace under the byte
    mask; and (PR 52) that workspace's fill: 8 heads' keys and values of
    69 632 latent rows, 1024 rows a step."""
    from comfyui_distributed_tpu.models.llm_glm import GlmConfig
    from comfyui_distributed_tpu.ops import index_select_attention as ops

    cfg = GlmConfig.glm_share()
    C, S = cfg.prefill_chunk_tokens, 17 * cfg.prefill_chunk_tokens
    n, J, di = cfg.select_rows, cfg.index_n_heads, cfg.index_head_dim
    g, d = ops.HEADS_PER_PASS, cfg.v_head_dim
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == d == 256

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    start = arg((), jnp.int32)
    if kernel == "index_score_sums":
        lowered = ops.index_score_sums.lower(
            arg((J, n, di)), arg((n, J), jnp.float32), arg((S, di)), start,
            block_q=ops.INDEX_TILE[0], block_k=ops.INDEX_TILE[1],
            interpret=False)
        out = f"f32[{n},{S}]"
    elif kernel == "index_select_keep":
        assert (n, S, ops.SELECT_ROWS, ops.select_tile(S)) \
            == (1024, 69632, 64, 4096)
        lowered = ops.index_select_keep.lower(
            arg((n, S), jnp.float32), start, topk=cfg.index_topk,
            rows=ops.SELECT_ROWS, tile=ops.select_tile(S), interpret=False)
        out = f"s8[{n},{S}]"
    elif kernel == "index_masked_mha":
        lowered = ops.index_masked_mha.lower(
            arg((C, g * d)), arg((S, g * d)), arg((S, g * d)),
            arg((C, S), jnp.int8), start, num_heads=g,
            block_q=ops.CORE_TILE[0], block_k=ops.CORE_TILE[1],
            part=ops.CORE_PART, interpret=False)
        out = f"bf16[{C},{g * d}]"
    else:
        lowered = ops.index_fill_kv.lower(
            arg((S, cfg.kv_lora_rank)), arg((S, d)),
            arg((cfg.kv_lora_rank, g * d)), arg((cfg.kv_lora_rank, g * d)),
            start, num_heads=g, nope=cfg.qk_nope_head_dim,
            block_rows=ops.FILL_ROWS, interpret=False)
        out = f"bf16[{S},{g * d}]"
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and kernel in text and out in text


def test_the_index_selecting_rewriters_programs_fit_beside_sdxl(chip,
                                                               monkeypatch):
    """Both language programs of ``glm-5.brief64k-sdxl8`` at the cell's
    sizes (65 536 + 128 tokens, published widths, 5 layers): they compile
    for the chip and leave room for SDXL's segment program (4.79 + 0.56
    GiB) in 15.75 GiB; the prefill holds FOUR Pallas call sites a layer —
    scores, selection, the workspace's fill (PR 52), attention under the
    mask —; nothing ``[heads, chunk, rows]`` exists in float32 (the scores
    are ``[1024, 69 632]``, a quarter of a chunk's queries at a time, the
    mask a byte a pair) and no float32 product of the fill's either (a
    group's ``[4096, 8, 448]`` before PR 52); no buffer of the workspace's
    size is written as a constant; and
    ``llm_decode`` holds no Pallas call — one token scores, selects
    (``top_k``) and gathers in XLA."""
    from comfyui_distributed_tpu.models.llm_glm import GlmConfig
    from comfyui_distributed_tpu.ops import index_select_attention as ops

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = GlmConfig.glm_share()
    compiled = loop_copies.compiled_programs(cfg, 65536, 128, chip)
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    text = compiled["llm_prefill"].as_text()
    calls = _pallas_calls(text)
    assert len(calls) == 4 * cfg.num_hidden_layers == 20
    for name in GLM_KERNELS:
        assert len(_pallas_calls(text, name)) == cfg.num_hidden_layers
    rows = 17 * cfg.prefill_chunk_tokens
    wide = [math.prod(int(n) for n in shape.split(","))
            for shape in re.findall(r"f32\[([\d,]+)\]", text)
            if str(rows) in shape.split(",")]
    assert wide and max(wide) == cfg.select_rows * rows
    C, g = cfg.prefill_chunk_tokens, ops.HEADS_PER_PASS
    kv = cfg.qk_nope_head_dim + cfg.v_head_dim
    assert not re.findall(rf"f32\[{C},(?:{g},{kv}|{g * kv})\]", text)
    assert not re.findall(
        rf"bf16\[{rows},{g * cfg.v_head_dim}\]\S* broadcast\(", text)
    mem = compiled["llm_prefill"].memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    assert 7.0 < prefill_gib < 7.6 and prefill_gib + sdxl < 15.75 - 1.0
    text = compiled["llm_decode"].as_text()
    assert "tpu_custom_call" not in text                   # decode is XLA
    mem = compiled["llm_decode"].memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 5.8 < decode_gib < 6.5 and decode_gib + sdxl < 15.75 - 1.0


KEYE_KERNELS = ("index_score_sums", "index_select_keep", "index_masked_gqa",
                "expert_tiles_mlp")


@pytest.mark.parametrize("kernel", KEYE_KERNELS)
def test_the_index_selecting_gqa_kernels_compile_at_the_served_geometry(
        chip, kernel):
    """The three kernels as ``keye-vl-2.0-30b-a3b``'s prefill calls them
    (PR 53), alone: the scores of 1024 queries × 16 index heads of 64 — half
    the matrix unit's depth — against 69 632 index keys at THIS module's
    tile; GLM's selection kernel as it is; and 4096 queries of 32 heads over
    4 K/V heads of 128 under the byte mask, K and V tiles read out of the
    ``[69 632, 1024]`` row buffer where they lie, a step's rows in the parts
    ``core_part`` says over a grid whose K axis is traced (PR 65)."""
    from comfyui_distributed_tpu.models.llm_keye import KeyeConfig
    from comfyui_distributed_tpu.ops import index_gqa_attention as gqa_ops
    from comfyui_distributed_tpu.ops import index_select_attention as ops

    cfg = KeyeConfig.keye_share()
    C, S = cfg.prefill_chunk_tokens, 17 * cfg.prefill_chunk_tokens
    n, J, di = cfg.select_rows, cfg.indexer_num_heads, cfg.indexer_head_dim
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    start = arg((), jnp.int32)
    if kernel == "index_score_sums":
        lowered = ops.index_score_sums.lower(
            arg((J, n, di)), arg((n, J), jnp.float32), arg((S, di)), start,
            block_q=gqa_ops.INDEX_TILE[0], block_k=gqa_ops.INDEX_TILE[1],
            interpret=False)
        out = f"f32[{n},{S}]"
    elif kernel == "index_select_keep":
        lowered = ops.index_select_keep.lower(
            arg((n, S), jnp.float32), start, topk=cfg.topk,
            rows=ops.SELECT_ROWS, tile=ops.select_tile(S), interpret=False)
        out = f"s8[{n},{S}]"
    elif kernel == "index_masked_gqa":
        lowered = gqa_ops.index_masked_gqa.lower(
            arg((C, H * d)), arg((S, 2 * G * d)), arg((C, S), jnp.int8),
            start, num_heads=H, num_kv_heads=G,
            block_q=gqa_ops.CORE_TILE[0], block_k=gqa_ops.CORE_TILE[1],
            interpret=False)
        out = f"bf16[{C},{H * d}]"
        # PR 65: the shipped tile by the shipped part — 64 parts of 64 rows
        # of one head a step, two float32 logit parts and the 4 MiB bias
        # alive at once — over a TRACED K extent (``start`` is an argument:
        # ``core_k_steps`` of it is the grid's last axis), under the scoped
        # VMEM the kernel asks for itself
        assert gqa_ops.core_part(gqa_ops.CORE_TILE[0]) == gqa_ops.CORE_PART \
            < gqa_ops.CORE_TILE[0]
        assert str(gqa_ops._VMEM_LIMIT_BYTES) in lowered.as_text()
    else:
        # the expert layer's tiles: 128 experts' [2048, 1536] and [768, 2048]
        # streamed behind the tile -> expert table, 256 tiles at the most
        from comfyui_distributed_tpu.ops import expert_share, expert_stream

        E, D, F, tile = (cfg.num_experts, cfg.hidden_size,
                         cfg.moe_intermediate_size, cfg.expert_tile)
        n = C * cfg.num_experts_per_tok // tile + E
        lowered = expert_stream.expert_tiles_mlp.lower(
            arg((n * tile, D)), arg((n,), jnp.int32), start,
            arg((E, D, 2 * F)), arg((E, F, D)), tile=tile,
            act=expert_share.silu_gate, interpret=False)
        out = f"f32[{n * tile},{D}]"
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and kernel in text and out in text


def test_the_all_held_rewriters_programs_fit_beside_sdxl(chip, monkeypatch):
    """Both language programs of ``keye-vl-2.0-30b-a3b.brief64k-sdxl8`` at
    the cell's sizes (65 536 + 128 tokens, published widths, 4 layers, all
    128 experts of each, the whole vocabulary): they compile for the chip
    and leave room for SDXL's segment program (4.79 + 0.56 GiB) in 15.75
    GiB; the prefill holds FOUR Pallas call sites a layer — scores,
    selection, attention under the mask (no workspace, no fill) and the
    expert layer's tiles —; the
    ``[69 632, 1024]`` K/V rows are never copied into a per-head layout
    (nothing ``[4, 69 632, 128]`` exists); nothing ``[heads, chunk, rows]``
    exists in float32; and ``llm_decode`` holds no Pallas call — one token
    scores, selects (``top_k``), gathers and reads its 8 experts in XLA."""
    from comfyui_distributed_tpu.models.llm_keye import KeyeConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = KeyeConfig.keye_share()
    compiled = loop_copies.compiled_programs(cfg, 65536, 128, chip)
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    text = compiled["llm_prefill"].as_text()
    assert len(_pallas_calls(text)) == 4 * cfg.num_hidden_layers == 16
    for name in KEYE_KERNELS:
        assert len(_pallas_calls(text, name)) == cfg.num_hidden_layers
    rows = 17 * cfg.prefill_chunk_tokens
    wide = [math.prod(int(n) for n in shape.split(","))
            for shape in re.findall(r"f32\[([\d,]+)\]", text)
            if str(rows) in shape.split(",")]
    assert wide and max(wide) == cfg.select_rows * rows
    G, d = cfg.num_key_value_heads, cfg.head_dim
    assert not re.findall(rf"bf16\[{G},{rows},{d}\]", text)
    mem = compiled["llm_prefill"].memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    assert 7.2 < prefill_gib < 8.0 and prefill_gib + sdxl < 15.75 - 1.0
    text = compiled["llm_decode"].as_text()
    assert "tpu_custom_call" not in text                   # decode is XLA
    mem = compiled["llm_decode"].memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    assert 6.4 < decode_gib < 7.8 and decode_gib + sdxl < 15.75 - 1.0


@pytest.mark.parametrize("kernel", ["gqa_causal_mha", "expert_tiles_mlp"])
def test_the_latent_rewriters_kernels_compile_at_the_served_geometry(chip,
                                                                     kernel):
    """The two kernels as ``zaya1-8b``'s prefill calls them (PR 57), alone:
    the causal kernel at a THIRD geometry — 8 query heads over 2 K/V heads of
    128, a 4096-token chunk over the 131 072 rows of a context filled to its
    end, at the tile the preset ships — and the expert layer's tiles at 16
    experts of ``[2048, 4096]`` + ``[2048, 2048]`` WHOLE (24 MiB an expert,
    48 twice buffered of the kernel's 64 MiB: a tile of 256 rows still fits —
    one of 512 does not — and an axis over the inner width read slower on the
    chip: PERF.md §6, PR 57)."""
    from comfyui_distributed_tpu.models import llm_zaya
    from comfyui_distributed_tpu.ops import (expert_share, expert_stream,
                                             flash_latent)

    cfg = llm_zaya.ZayaConfig.zaya_share()
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    C = cfg.prefill_chunk_tokens
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if kernel == "gqa_causal_mha":
        S = jax.eval_shape(lambda: llm_zaya.empty_cache(
            cfg, 130944 + 128))["k"][0].shape[1]
        assert S == 131072 and S % cfg.attn_block_k == 0 \
            and C % cfg.attn_block_q == 0
        lowered = flash_latent.gqa_causal_mha.lower(
            arg((C, H * d)), arg((G, S, d)), arg((G, S, d)), scalar,
            num_heads=H, block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
            interpret=False)
        out = f"bf16[{C},{H * d}]"
    else:
        E, D, F, tile = (cfg.num_experts, cfg.hidden_size,
                         cfg.moe_intermediate_size, cfg.expert_tile)
        n = C * cfg.num_experts_per_tok // tile + E
        assert n == 32
        lowered = expert_stream.expert_tiles_mlp.lower(
            arg((n * tile, D)), arg((n,), jnp.int32), scalar,
            arg((E, D, 2 * F)), arg((E, F, D)), tile=tile,
            act=expert_share.silu_gate, interpret=False)
        out = f"f32[{n * tile},{D}]"
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and kernel in text and out in text


def test_the_latent_rewriters_programs_fit_beside_sdxl(chip, monkeypatch):
    """Both language programs of ``zaya1-8b.ctx128k-sdxl8`` at the cell's
    sizes (130 944 + 128 tokens = the published context, published widths,
    10 layers, all 16 experts of each, the whole vocabulary): they compile
    for the chip and leave room for SDXL's segment program (4.79 + 0.56 GiB)
    in 15.75 GiB; the prefill holds TWO Pallas call sites a layer — one
    ``gqa_causal_mha`` and the expert layer's tiles —; the K and V buffers
    are written where they lie; nothing ``[chunk, rows]`` exists in float32;
    and ``llm_decode`` holds no Pallas call. The peak is printed."""
    from comfyui_distributed_tpu.models.llm_zaya import ZayaConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = ZayaConfig.zaya_share()
    compiled = loop_copies.compiled_programs(cfg, 130944, 128, chip)
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    text = compiled["llm_prefill"].as_text()
    layers = cfg.num_hidden_layers
    assert len(_pallas_calls(text)) == 2 * layers == 20
    for name in ("gqa_causal_mha", "expert_tiles_mlp"):
        assert len(_pallas_calls(text, name)) == layers
    rows, C = 131072, cfg.prefill_chunk_tokens
    assert not re.findall(rf"f32\[(\d+,)?{C},{rows}\]", text)
    buffer = f"bf16[{cfg.num_key_value_heads},{rows},{cfg.head_dim}]"
    assert buffer in text
    assert not [line for line in text.splitlines()
                if buffer in line.split("=")[0] and " copy(" in line]
    mem = compiled["llm_prefill"].memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    text = compiled["llm_decode"].as_text()
    assert "tpu_custom_call" not in text                   # decode is XLA
    mem = compiled["llm_decode"].memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    print(f"zaya1-8b at 130944 + 128: llm_prefill {prefill_gib:.2f} GiB, "
          f"llm_decode {decode_gib:.2f} GiB beside SDXL's {sdxl:.2f}")
    assert 6.0 < prefill_gib < 6.8 and prefill_gib + sdxl < 15.75 - 2.0
    assert 7.2 < decode_gib < 8.0 and decode_gib + sdxl < 15.75 - 2.0


def test_the_retention_kernel_compiles_at_the_served_geometry(chip):
    """``power_retention`` as ``brumby-14b-base``'s prefill calls it (PR 61),
    alone: 40 query heads over 8 K/V heads of 128, a chunk of 4096 rows in
    blocks of 256, the state ``[8, 128, 8320]`` float32 resident a head —
    ``φ`` of 5 × 256 rows in a 21 MB VMEM scratch beside it."""
    from comfyui_distributed_tpu.models.llm_brumby import BrumbyConfig
    from comfyui_distributed_tpu.ops import power_retention

    cfg = BrumbyConfig.brumby_stage()
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    C, D = cfg.prefill_chunk_tokens, cfg.state_width

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    lowered = power_retention.power_retention.lower(
        arg(G, d, D), arg(G, d, d), arg(C, H * d), arg(C, G * d),
        arg(C, G * d), arg(C, G), heads=H // G, dtype="bfloat16",
        block=cfg.retention_block, interpret=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and f"f32[{C},{H * d}]" in text
    assert f"f32[{G},{d},{D}]" in text


def test_the_retention_rewriters_programs_fit_beside_sdxl(chip, monkeypatch):
    """Both language programs of ``brumby-14b-base.ctx32k-sdxl8`` at the
    cell's sizes (32 640 + 128 tokens = the published context, published
    widths, 6 layers, the whole vocabulary at both ends): they compile for
    the chip and leave room for SDXL's segment program (4.79 + 0.56 GiB) in
    15.75 GiB; the prefill holds ONE Pallas call site a layer, the retention
    walk; nothing holds ``φ`` of a chunk's rows (``[4096, 40, D]``) or a
    state a block (``[16, 8, D, ·]``) — ``D`` appears in the states'
    ``[8, 128, 8320]`` alone; ``llm_decode`` holds ONE Pallas call a layer too,
    the step's pass over the state. The peak
    is printed."""
    from comfyui_distributed_tpu.models.llm_brumby import BrumbyConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = BrumbyConfig.brumby_stage()
    compiled = loop_copies.compiled_programs(cfg, 32640, 128, chip)
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    text = compiled["llm_prefill"].as_text()
    layers, D = cfg.num_hidden_layers, cfg.state_width
    assert len(_pallas_calls(text)) == layers == 6
    assert len(_pallas_calls(text, "power_retention")) == layers
    state = f"[{cfg.num_key_value_heads},{cfg.head_dim},{D}]"
    assert set(re.findall(rf"\[[\d,]*\b(?:{D}|8256)\b[\d,]*\]", text)) \
        == {state}
    mem = compiled["llm_prefill"].memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    text = compiled["llm_decode"].as_text()
    assert len(_pallas_calls(text, "retention_read_update")) == layers
    mem = compiled["llm_decode"].memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    print(f"brumby-14b-base at 32640 + 128: llm_prefill {prefill_gib:.2f} "
          f"GiB, llm_decode {decode_gib:.2f} GiB beside SDXL's {sdxl:.2f}")
    assert 7.0 < prefill_gib < 8.0 and prefill_gib + sdxl < 15.75 - 2.0
    assert 6.8 < decode_gib < 7.6 and decode_gib + sdxl < 15.75 - 2.0


def test_the_wide_kernel_compiles_at_the_served_geometry(chip):
    """``flash_latent._gqa_kernel`` as ``mimo-v2-flash``'s full layers call it
    (PR 64), alone: 64 head-major query heads of 192 over 4 K/V heads, values
    128 wide, a 4096-token chunk over the 128 k buffer rounded to the K
    block, at the 2048 × 2048 tile it ships with and the parts ``step_rows``
    says — a tile whose last dimension is 192, no multiple of the 128 lanes,
    is where the compiler would refuse first."""
    from comfyui_distributed_tpu.models import llm_mimo
    from comfyui_distributed_tpu.ops import gqa_sink_attention

    cfg = llm_mimo.MimoConfig.mimo_stage()
    H, G = cfg.num_attention_heads, cfg.num_key_value_heads
    dk, dv, C = cfg.head_dim, cfg.v_head_dim, cfg.prefill_chunk_tokens
    cache = jax.eval_shape(lambda: llm_mimo.empty_cache(cfg, 131072 + 128))
    S = cache["k"][0].shape[1]
    assert cache["k"][0].shape == (G, 133120, dk) and cache["v"][0].shape \
        == (G, 133120, dv) and S % cfg.attn_block_k == 0
    assert cache["k"][1].shape == (8, 128, dk)          # a window layer's ring

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    lowered = gqa_sink_attention.gqa_wide_causal_mha.lower(
        arg((H, C, dk)), arg((G, S, dk)), arg((G, S, dv)),
        arg((), jnp.int32), block_q=cfg.attn_block_q,
        block_k=cfg.attn_block_k, interpret=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and f"bf16[{C},{H * dv}]" in text


def test_the_window_and_sink_rewriters_programs_fit_beside_sdxl(chip,
                                                                monkeypatch):
    """Both language programs of ``mimo-v2-flash.brief128k-sdxl8`` at the
    cell's sizes (131 072 + 128 tokens, published widths, 7 layers, 16 experts
    held, an eighth of the vocabulary): they compile for the chip and leave
    room for SDXL's segment program (4.79 + 0.56 GiB) in 15.75 GiB;
    ``llm_prefill`` holds ONE Pallas call site a FULL layer — two: the band of
    the five window layers is XLA's — and nothing holds a chunk's logits
    against ``[ring ; chunk]`` whole (``[…, 4096·8, 4224]``); ``llm_decode``
    holds none. The peak is printed."""
    from comfyui_distributed_tpu.models.llm_mimo import MimoConfig

    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    cfg = MimoConfig.mimo_stage()
    compiled = loop_copies.compiled_programs(cfg, 131072, 128, chip)
    gib, sdxl = 2.0 ** 30, 4.79 + 0.56
    text = compiled["llm_prefill"].as_text()
    assert len(_pallas_calls(text)) == 2
    assert len(_pallas_calls(text, "gqa_wide_causal_mha")) == 2
    assert not re.findall(r"f32\[[\d,]*\b4224\]", text)
    mem = compiled["llm_prefill"].memory_analysis()
    prefill_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes) / gib
    text = compiled["llm_decode"].as_text()
    assert not _pallas_calls(text)
    mem = compiled["llm_decode"].memory_analysis()
    decode_gib = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib
    print(f"mimo-v2-flash at 131072 + 128: llm_prefill {prefill_gib:.2f} "
          f"GiB, llm_decode {decode_gib:.2f} GiB beside SDXL's {sdxl:.2f}")
    assert 7.8 < prefill_gib < 9.0 and prefill_gib + sdxl < 15.75 - 1.0
    assert 7.6 < decode_gib < 8.6 and decode_gib + sdxl < 15.75 - 1.0
