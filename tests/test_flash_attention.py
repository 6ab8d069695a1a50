"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh;
numerics checked against dense attention)."""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest

from comfyui_distributed_tpu.ops.flash_attention import flash_attention

pytestmark = pytest.mark.slow  # compile-heavy: builds/jits real model stacks


def dense_reference(q, k, v):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def rand_qkv(key, B=1, Nq=128, Nk=128, H=2, D=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Nq, H, D), dtype)
    k = jax.random.normal(kk, (B, Nk, H, D), dtype)
    v = jax.random.normal(kv, (B, Nk, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("layout", ["bh", "packed"])
class TestNumerics:
    """Both kernel layouts against the dense reference (``packed`` at a
    packed-illegal geometry is the classic call)."""

    def test_block_aligned(self, layout):
        q, k, v = rand_qkv(jax.random.key(0), Nq=256, Nk=256)
        out = flash_attention(q, k, v, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_ragged_lengths_masked(self, layout):
        """Nq/Nk not multiples of the block sizes → padding is masked out."""
        q, k, v = rand_qkv(jax.random.key(1), Nq=100, Nk=77)
        out = flash_attention(q, k, v, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        assert out.shape == (1, 100, 2, 64)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_multi_kv_blocks_accumulate(self, layout):
        """Nk spanning several K blocks exercises the streaming-softmax
        carry (running max / denominator / accumulator rescale)."""
        q, k, v = rand_qkv(jax.random.key(2), Nq=128, Nk=512)
        out = flash_attention(q, k, v, block_k=128, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16_inputs(self, layout):
        q, k, v = rand_qkv(jax.random.key(3), Nq=128, Nk=256,
                           dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(np.float32), ref,
                                   atol=2e-2, rtol=2e-2)

    def test_extreme_logits_stable(self, layout):
        """Large-magnitude logits must not overflow exp (running-max
        subtraction)."""
        q, k, v = rand_qkv(jax.random.key(4), Nq=128, Nk=256)
        q = q * 30.0
        out = flash_attention(q, k, v, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)

    def test_batch_and_heads(self, layout):
        q, k, v = rand_qkv(jax.random.key(5), B=2, Nq=64, Nk=64, H=4, D=32)
        out = flash_attention(q, k, v, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_shape(self, layout):
        """Cross attention: 77-token text context vs image queries."""
        q, k, v = rand_qkv(jax.random.key(6), Nq=256, Nk=77)
        out = flash_attention(q, k, v, interpret=True, layout=layout)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestShardMap:
    def test_inside_shard_map_dp(self):
        """The production path: attention running inside the dp-sharded
        generation program (vma must propagate to the pallas out_shape)."""
        from jax.sharding import PartitionSpec as P

        from comfyui_distributed_tpu.parallel.mesh import build_mesh

        mesh = build_mesh({"dp": 8})
        q, k, v = rand_qkv(jax.random.key(8), B=8, Nq=64, Nk=64, H=2, D=32)

        def per_shard(q, k, v):
            return flash_attention(q, k, v, interpret=True)

        f = jax.jit(shard_map(
            per_shard, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P("dp")),
            out_specs=P("dp")))
        out = f(q, k, v)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestDispatch:
    def test_full_attention_env_toggle(self, monkeypatch):
        """CDT_FLASH_ATTENTION is the one environment variable that takes
        part: =0 is XLA whatever the geometry, =1 reaches flash on this
        CPU host (the interpreter), unset is XLA off-TPU."""
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.setenv("CDT_FLASH_ATTENTION", "0")
        assert attn.select_kernel(1 << 20, 1 << 20, 10, 64).tier == "xla"
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        assert attn.select_kernel(64, 64, 2, 64).tier == "bh"
        assert attn.select_kernel(4096, 4096, 16, 64).tier == "packed"
        monkeypatch.delenv("CDT_FLASH_ATTENTION")
        assert attn.select_kernel(4096, 4096, 16, 64).tier == "xla"

    def test_seq_length_gate(self):
        """r04: the classic call is gated on q length — below 8192 the
        XLA fused lowering wins on TPU (measured: scripts/mfu_probe.py,
        SDXL 1024² flash 0.1763 s/fwd vs XLA 0.1677), so a packed-illegal
        short sequence resolves to XLA; a caller promised flash gets the
        classic call at any length."""
        from comfyui_distributed_tpu.ops import attention as attn

        assert attn.BH_MIN_Q == 8192
        assert attn.policy_choice(8191, 8191, 5, 64).tier == "xla"
        assert attn.policy_choice(8192, 8192, 5, 64).tier == "bh"
        assert attn.policy_choice(64, 64, 5, 64, flash_only=True).tier \
            == "bh"

    def test_prefer_flash_safe_off_tpu(self, monkeypatch):
        """prefer_flash skips the seq-length gate but NOT the platform
        check: on this CPU host it must fall through to the XLA path
        (a pallas call would need interpret mode) and still be exact.
        The offload executor relies on this — its block programs set
        prefer_flash unconditionally (OOM-measured necessity on TPU)."""
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        q, k, v = rand_qkv(jax.random.key(11), Nq=32, Nk=32)
        out = attn.full_attention(q, k, v, prefer_flash=True)
        np.testing.assert_allclose(out, dense_reference(q, k, v),
                                   atol=2e-5, rtol=2e-5)

    def test_full_attention_uses_flash_when_forced(self, monkeypatch):
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        q, k, v = rand_qkv(jax.random.key(7), Nq=64, Nk=64)
        out = attn.full_attention(q, k, v)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestLayoutVariants:
    """The packed-heads ([B,N,H·D]-native) pallas call and the classic
    pre-transposed [B·H,N,D] (bh) call are the same math — packed keeps
    q/k/v in the QKV projection's own layout and splits heads inside the
    kernel (r04 boundary-relayout fix, docs/roofline.md finding 1)."""

    @pytest.mark.parametrize("shape", [
        (2, 300, 4, 64, 300),     # padded tails on both q and k
        (1, 1024, 10, 64, 77),    # SDXL cross-attention geometry
        (2, 513, 3, 128, 200),    # D=128, odd lengths
        (1, 600, 24, 128, 500),   # FLUX geometry: H*D=3072 is 24 head
                                  # groups on the grid, no width ceiling
                                  # (see TestPackedBlocks)
    ])
    def test_packed_matches_bh(self, shape):
        from comfyui_distributed_tpu.ops.flash_attention import flash_attention

        b, nq, h, d, nk = shape
        q = jax.random.normal(jax.random.key(0), (b, nq, h, d))
        k = jax.random.normal(jax.random.key(1), (b, nk, h, d))
        v = jax.random.normal(jax.random.key(2), (b, nk, h, d))
        a = flash_attention(q, k, v, interpret=True, layout="packed")
        b_ = flash_attention(q, k, v, interpret=True, layout="bh")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a), dense_reference(q, k, v),
                                   atol=5e-2, rtol=5e-2)


class TestShapeGate:
    """r04 final gate: on TPU (simulated here by patching jax.devices)
    the one policy picks per shape — packed-legal layouts engage at
    q ≥ 1024 with K ≥ 256 (measured crossover, docs/roofline.md finding
    1a), packed-illegal layouts keep the classic 8192 gate. Geometries
    inside and at the edges of a label's bucket: the policy alone answers."""

    @pytest.fixture()
    def tier(self, monkeypatch):
        import types

        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        fake = types.SimpleNamespace(platform="tpu")
        monkeypatch.setattr(attn.jax, "devices", lambda *a: [fake])

        def tier(q_len, kv_len, num_heads, head_dim):
            return attn.select_kernel(q_len, kv_len, num_heads,
                                      head_dim).tier
        return tier

    def test_packed_legal_engages_at_sdxl_lengths(self, tier):
        # SDXL self-attention: 4096 tokens, 10 heads × 64
        assert tier(4096, 4096, 10, 64) == "packed"
        # the 32² block: 1024 tokens — exactly at the packed floor
        assert tier(1024, 1024, 20, 64) == "packed"
        assert tier(512, 512, 20, 64) == "xla"

    def test_short_kv_cross_attention_stays_on_xla(self, tier):
        # SDXL cross-attention: K = 77 text tokens → one mostly-padding
        # K block, measured behind XLA
        assert tier(4096, 77, 10, 64) == "xla"

    def test_packed_illegal_keeps_classic_gate(self, tier):
        # H·D = 5·64 is not a whole number of 128-lane groups → classic
        # call, 8192 gate
        assert tier(4608, 4608, 5, 64) == "xla"
        assert tier(9000, 9000, 5, 64) == "bh"
        # FLUX's H·D = 3072 was past the old tile's width ceiling; a tile
        # is one group wide now, so it is packed at its real length
        assert tier(4608, 4608, 24, 128) == "packed"

    def test_floors_hold_exact_lengths_not_buckets(self, tier):
        # 830 tokens share the 1024 bucket of the label; the policy
        # reads the length itself (PACKED_MIN_Q = 832, PR 55's reading)
        assert tier(830, 830, 16, 64) == "xla"
        assert tier(1024, 255, 16, 64) == "xla"
        assert tier(1024, 256, 16, 64) == "packed"

    def test_short_kv_long_q_falls_through_to_classic_gate(self, tier):
        # packed-legal geometry whose KV floor fails must still reach
        # the classic bh gate at very long q (streamed-softmax memory
        # win), not silently drop flash entirely (r04 advisor finding)
        assert tier(16384, 77, 10, 64) == "bh"
        assert tier(4096, 77, 10, 64) == "xla"

    def test_packed_layout_requires_lane_aligned_head_dim(self, monkeypatch):
        # H=128, D=16 fills whole lane groups but with eight heads a
        # group, a shape class never measured — excluded: asked for
        # packed (or nothing), such a call runs the classic kernel
        from comfyui_distributed_tpu.ops import flash_attention as fa

        assert not fa._packed_legal(128, 16)
        calls = _packed_call_spy(monkeypatch)
        q, k, v = rand_qkv(jax.random.key(13), Nq=64, Nk=64, H=128, D=16)
        for layout in ("packed", None):
            out = flash_attention(q, k, v, interpret=True, layout=layout)
            np.testing.assert_allclose(out, dense_reference(q, k, v),
                                       atol=2e-5, rtol=2e-5)
        assert not calls
        q, k, v = rand_qkv(jax.random.key(14), Nq=64, Nk=64, H=2, D=64)
        flash_attention(q, k, v, interpret=True)
        assert calls == [(64, 128)]

    def test_block_arguments_validated(self):
        """Non-positive or non-(8,128)-divisible blocks raise a
        descriptive error at the call instead of letting pallas fail
        deep in Mosaic lowering (ISSUE 8 satellite) — in either layout,
        and in the checks the dispatcher resolves a choice's blocks with."""
        from comfyui_distributed_tpu.ops import flash_attention as fa

        q, k, v = rand_qkv(jax.random.key(12), Nq=256, Nk=512)
        for layout in ("packed", "bh"):
            with pytest.raises(ValueError, match="block_q=0"):
                flash_attention(q, k, v, block_q=0, interpret=True,
                                layout=layout)
            with pytest.raises(ValueError, match="multiple of 8"):
                flash_attention(q, k, v, block_q=100, interpret=True,
                                layout=layout)
            with pytest.raises(ValueError, match="multiple of 128"):
                flash_attention(q, k, v, block_q=256, block_k=-64,
                                interpret=True, layout=layout)
            with pytest.raises(ValueError, match="multiple of 128"):
                flash_attention(q, k, v, block_k=200, interpret=True,
                                layout=layout)
        assert fa._packed_legal(2, 64)
        for check in (fa._check_blocks, fa.resolve_flash_blocks):
            with pytest.raises(ValueError, match="multiple of 128"):
                check(256, 200)


def _packed_call_spy(monkeypatch):
    """Record the (block_q, block_k) of every packed pallas call."""
    from comfyui_distributed_tpu.ops import flash_attention as fa

    calls = []
    orig = fa._flash_mha_packed

    def spy(*args, **kw):
        calls.append((kw.get("block_q"), kw.get("block_k")))
        return orig(*args, **kw)

    monkeypatch.setattr(fa, "_flash_mha_packed", spy)
    return calls


class TestPackedBlocks:
    """The packed tier's blocks and VMEM model (PR 25): heads are a grid
    axis, a tile is one 128-lane head group wide, and ``block_k`` comes
    from the shape — the whole padded sequence where it fits."""

    @pytest.mark.parametrize("case", [
        # (Nq, Nk, D, itemsize, requested, expected)
        (4173, 4173, 64, 2, (None, None), (464, 4224)),    # SD3: 9 x 464
        (1024, 1024, 64, 2, (None, None), (512, 1024)),    # SDXL 32^2
        (4096, 4096, 64, 2, (None, None), (512, 4096)),    # SDXL 64^2
        (4608, 4608, 128, 2, (None, None), (512, 4608)),   # FLUX
        (14040, 14040, 128, 2, (None, None), (512, 14080)),  # WAN 480p
        (4173, 4173, 64, 4, (None, None), (464, 4224)),    # f32 operands
        (4173, 4173, 64, 2, (256, None), (256, 4224)),     # q requested
        (4173, 4173, 64, 2, (None, 512), (464, 512)),      # K requested
        (4173, 4173, 64, 2, (256, 512), (256, 512)),       # both: as asked
        (100, 77, 64, 2, (None, None), (112, 128)),        # one lane tile
    ], ids=lambda c: f"q{c[0]}.kv{c[1]}.d{c[2]}.b{c[3]}.req{c[4]}")
    def test_blocks_from_the_shape(self, case):
        from comfyui_distributed_tpu.ops.flash_attention import _packed_blocks

        nq, nk, d, itemsize, requested, expected = case
        assert _packed_blocks(nq, nk, d, itemsize, *requested) == expected

    def test_long_sequence_streams_in_the_longest_chunks_that_fit(self):
        """K/V that do not fit beside the logits (a 32 k-token video at
        f32) stream over the grid in equal lane-aligned chunks."""
        from comfyui_distributed_tpu.ops.flash_attention import (
            _PACKED_VMEM_BUDGET_BYTES, _packed_blocks, _packed_vmem_bytes)

        bq, bk = _packed_blocks(32760, 32760, 128, 4)
        assert bk < 32760 and bk % 128 == 0
        chunks = -(-32760 // bk)
        assert chunks * bk - 32760 < 128 * chunks       # padding < a slab each
        assert _packed_vmem_bytes(128, bq, bk, 4, True) \
            <= _PACKED_VMEM_BUDGET_BYTES
        # one chunk fewer would not have fit
        longer = -(-32768 // (chunks - 1) // 128) * 128
        assert _packed_vmem_bytes(128, bq, longer, 4, chunks > 2) \
            > _PACKED_VMEM_BUDGET_BYTES

    def test_vmem_model_grows_with_the_slab_not_the_width(self):
        """What broke the r05 WAN probe (25.09 MB at H·D=1536, 256/1024
        blocks) was the full-width tile. The model no longer takes H·D:
        1024 K rows of one group are 0.5 MB, and the body term follows
        the in-body slab, not block_k."""
        from comfyui_distributed_tpu.ops.flash_attention import (
            _PACKED_SLAB, _PACKED_VMEM_BUDGET_BYTES, _packed_slab,
            _packed_vmem_bytes)

        assert _packed_vmem_bytes(128, 256, 1024, 2, True) < 8 * 2 ** 20
        assert _packed_slab(4224) == 1408 and _packed_slab(1024) == 1024
        assert all(_packed_slab(n) <= _PACKED_SLAB
                   for n in range(128, 40000, 128))
        grow = (_packed_vmem_bytes(64, 464, 8448, 2)
                - _packed_vmem_bytes(64, 464, 4224, 2))
        assert grow == 2 * 2 * 4224 * 128 * 2           # K/V tiles only
        assert _packed_vmem_bytes(64, 464, 4224, 2) \
            < _PACKED_VMEM_BUDGET_BYTES

    def test_requested_blocks_past_the_budget_raise(self):
        """In the function and in the call."""
        from comfyui_distributed_tpu.ops.flash_attention import _packed_blocks

        with pytest.raises(ValueError, match="VMEM"):
            _packed_blocks(16384, 16384, 128, 2, 4096, 16384)
        q, k, v = (jax.ShapeDtypeStruct((1, 16384, 1, 128), jnp.bfloat16),) * 3
        with pytest.raises(ValueError, match="VMEM"):
            jax.eval_shape(
                lambda q, k, v: flash_attention(q, k, v, block_q=4096,
                                                block_k=16384,
                                                interpret=True), q, k, v)

    @pytest.mark.parametrize("H,D,legal", [
        (24, 128, True), (24, 64, True), (10, 64, True), (2, 192, True),
        (5, 64, False), (128, 16, False), (3, 80, False), (1, 192, False),
    ])
    def test_geometric_legality(self, H, D, legal):
        from comfyui_distributed_tpu.ops.flash_attention import _packed_legal

        assert _packed_legal(H, D) is legal

    def test_explicit_packed_at_flux_width_runs_packed(self, monkeypatch):
        """Acceptance: the FLUX geometry no longer falls back to the
        classic call — an explicit packed request at H·D=3072 computes
        via the packed kernel, K resident, and matches the dense
        reference."""
        from comfyui_distributed_tpu.ops import flash_attention as fa

        calls = _packed_call_spy(monkeypatch)
        q, k, v = rand_qkv(jax.random.key(20), B=1, Nq=600, Nk=500,
                           H=24, D=128)
        out = fa.flash_attention(q, k, v, interpret=True, layout="packed")
        np.testing.assert_allclose(np.asarray(out),
                                   dense_reference(q, k, v),
                                   atol=5e-2, rtol=5e-2)
        assert calls, "packed kernel was not used at H·D=3072"
        assert calls[0] == (304, 512)   # 2 x 304 q rows, K in one tile


class TestPackedParity:
    """The packed blocking against ``jax.nn.dot_product_attention`` in
    f32: both group kinds (two D=64 heads, one D=128 head), K resident
    and streamed, ragged lengths, Nq != Nk, both operand dtypes."""

    CASES = [
        # id, B, Nq, Nk, H, D, block_q, block_k
        ("d64.even_groups.resident", 2, 333, 333, 4, 64, None, None),
        ("d64.odd_groups.resident", 1, 333, 333, 6, 64, None, None),
        ("d64.odd_groups.streamed", 1, 333, 333, 6, 64, 128, 128),
        ("d64.cross.resident", 2, 300, 77, 2, 64, None, None),
        ("d64.cross.streamed", 1, 200, 700, 2, 64, None, 256),
        ("d64.resident.two_slabs", 1, 300, 1700, 2, 64, None, None),
        ("d64.resident.long_tile", 1, 130, 300, 2, 64, 64, 512),
        ("d128.resident", 1, 333, 500, 3, 128, None, None),
        ("d128.streamed", 1, 333, 500, 3, 128, 64, 256),
        ("d128.cross.streamed", 2, 77, 333, 2, 128, None, 128),
        ("sd3.scaled", 2, 4096 // 16 + 77, 4096 // 16 + 77, 24, 64,
         None, None),
    ]

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", CASES, ids=lambda c: c[0])
    def test_matches_xla_reference(self, monkeypatch, case, dtype):
        from comfyui_distributed_tpu.ops import flash_attention as fa

        _, B, Nq, Nk, H, D, bq, bk = case
        calls = _packed_call_spy(monkeypatch)
        q, k, v = rand_qkv(jax.random.key(7), B=B, Nq=Nq, Nk=Nk, H=H, D=D,
                           dtype=jnp.dtype(dtype))
        out = fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                 interpret=True, layout="packed")
        ref = jax.nn.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32))
        assert out.dtype == q.dtype and out.shape == q.shape
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   atol=tol, rtol=tol)
        (got_bq, got_bk), = calls
        resident = got_bk >= Nk
        assert resident == ("resident" in case[0] or "scaled" in case[0])
        if bk is not None:
            assert got_bk == bk

    @pytest.mark.parametrize("block_k", [None, 1024])
    def test_long_k_tile_loops_over_its_slabs(self, monkeypatch, block_k):
        """A K tile of more than three clean slabs is walked by a loop,
        not unrolled (WAN's 14 k tokens are ten slabs): same numbers,
        resident and streamed, with padding that spans more than the
        last slab when the requested tile is longer than what is left."""
        from comfyui_distributed_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "_PACKED_SLAB", 256)
        q, k, v = rand_qkv(jax.random.key(11), Nq=200, Nk=1700, H=2, D=64)
        assert 1792 // fa._packed_slab(1792) > fa._PACKED_UNROLL_SLABS
        out = fa.flash_attention(q, k, v, block_k=block_k, interpret=True,
                                 layout="packed")
        np.testing.assert_allclose(out, dense_reference(q, k, v),
                                   atol=2e-5, rtol=2e-5)

    def test_power_of_two_scale_folds_bit_identically(self):
        """D=64: 1/sqrt(D) = 0.125 multiplies q instead of the logits and
        the result does not change by a bit; D=128's 0.0884 stays on the
        logits for bf16 operands (no second rounding of q)."""
        from comfyui_distributed_tpu.ops import flash_attention as fa

        assert fa._scale_folds_into_q(64, jnp.bfloat16)
        assert not fa._scale_folds_into_q(128, jnp.bfloat16)
        assert fa._scale_folds_into_q(128, jnp.float32)
        q, k, v = rand_qkv(jax.random.key(9), Nq=128, Nk=256, H=2, D=64,
                           dtype=jnp.bfloat16)
        a = fa.flash_attention(q, k, v, interpret=True, layout="packed")
        b = fa.flash_attention(q, k, v, interpret=True, layout="bh",
                               block_q=128, block_k=256)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# --- the two-segment packed call (MMDiT joint attention, PR 41) -------------


def _jaxpr_equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr its equations hold (a jit's,
    the Pallas kernel body's, a loop's)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _jaxpr_equations(sub)
    return n


# (id, B, text rows, image rows, H, D, q and k as buffers of their own)
JOINT_CASES = [
    ("sd3_text77_d64", 2, 77, 1024, 4, 64, False),
    ("flux_text512_d128", 1, 512, 1024, 2, 128, True),
    ("text_shorter_than_a_sublane_tile", 1, 5, 512, 2, 64, False),
    ("two_q_blocks_of_384", 1, 77, 768, 2, 64, False),
]


class TestJointSegments:
    """``ops.attention.joint_attention`` on the ``packed`` tier (a choice
    handed in; the Pallas interpreter) against float32 softmax attention
    over the concatenated rows: both outputs, operands as column groups of
    the ``qkv`` products or as buffers of their own."""

    @staticmethod
    def _segments(case, dtype, seed=0, scale_txt_v=1.0):
        from comfyui_distributed_tpu.ops.attention import Columns

        _, B, T, N, H, D, own_qk = case
        HD = H * D
        keys = jax.random.split(jax.random.key(seed), 6)
        txt_qkv = jax.random.normal(keys[0], (B, T, 3 * HD), dtype)
        img_qkv = jax.random.normal(keys[1], (B, N, 3 * HD), dtype)
        if scale_txt_v != 1.0:
            txt_qkv = txt_qkv.at[..., 2 * HD:].multiply(scale_txt_v)
        txt = [Columns(txt_qkv, g, 3) for g in range(3)]
        img = [Columns(img_qkv, g, 3) for g in range(3)]
        if own_qk:        # qk-norm / rope models: [B, N, H, D] buffers
            own = [jax.random.normal(k, (B, n, H, D), dtype)
                   for k, n in zip(keys[2:], (T, T, N, N))]
            txt[:2], img[:2] = own[:2], own[2:]
        return txt, img

    @staticmethod
    def _reference(txt, img, H):
        from comfyui_distributed_tpu.ops.attention import as_heads

        q, k, v = (jnp.concatenate([as_heads(t, H), as_heads(i, H)], axis=1)
                   for t, i in zip(txt, img))
        out = dense_reference(q, k, v)
        return out.reshape(*out.shape[:2], -1)

    @staticmethod
    def _packed_choice(case):
        from comfyui_distributed_tpu.ops.kernel_choice import KernelChoice

        _, _, T, N, _, _, _ = case
        return KernelChoice("packed", 512, -(-(T + N) // 128) * 128)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", JOINT_CASES, ids=lambda c: c[0])
    def test_two_segment_call_matches_softmax_over_the_joint_rows(
            self, case, dtype, monkeypatch):
        from comfyui_distributed_tpu.ops import attention as attn
        from comfyui_distributed_tpu.ops import flash_joint as fj

        _, B, T, N, H, D, _ = case
        calls = []
        real = fj.flash_joint_attention
        monkeypatch.setattr(
            fj, "flash_joint_attention",
            lambda *a, **kw: calls.append(a[3]) or real(*a, **kw))
        txt, img = self._segments(case, jnp.dtype(dtype))
        t_out, i_out = attn.joint_attention(
            txt, img, H, choice=self._packed_choice(case))
        assert len(calls) == 1, "the two-segment kernel did not run"
        assert calls[0].txt_rows % 128 == 0 and N % calls[0].img_block_q == 0
        assert t_out.shape == (B, T, H * D) and i_out.shape == (B, N, H * D)
        assert t_out.dtype == i_out.dtype == jnp.dtype(dtype)
        ref = self._reference(txt, img, H)
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(t_out.astype(np.float32), ref[:, :T],
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(i_out.astype(np.float32), ref[:, T:],
                                   atol=tol, rtol=tol)

    def test_the_text_tile_is_padded_with_zeros(self, monkeypatch):
        """The mask sets a padding column's probability to exactly 0, and
        0 × NaN is NaN in the value product: the text tile's padding rows
        must be zeros, not whatever lies past the text. With large text
        values the answer is right; with the padding poisoned every row
        of both outputs is lost."""
        from comfyui_distributed_tpu.ops import attention as attn
        from comfyui_distributed_tpu.ops import flash_joint as fj

        case = ("poison", 1, 77, 256, 2, 64, False)
        txt, img = self._segments(case, jnp.float32, seed=3,
                                  scale_txt_v=1e4)
        choice = self._packed_choice(case)
        t_out, i_out = attn.joint_attention(txt, img, 2, choice=choice)
        ref = self._reference(txt, img, 2)
        np.testing.assert_allclose(t_out, ref[:, :77], atol=5e-2, rtol=2e-5)
        np.testing.assert_allclose(i_out, ref[:, 77:], atol=5e-2, rtol=2e-5)

        def poisoned(x, rows):
            return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0)),
                           constant_values=jnp.nan)

        monkeypatch.setattr(fj, "_pad_rows", poisoned)
        fj._flash_mha_packed_joint.clear_cache()
        try:
            t_bad, i_bad = attn.joint_attention(txt, img, 2, choice=choice)
        finally:
            fj._flash_mha_packed_joint.clear_cache()
        assert np.isnan(np.asarray(t_bad)).all()
        assert np.isnan(np.asarray(i_bad)).all()

    @pytest.mark.parametrize("why,case,block_k", [
        ("ragged_image_rows", ("r", 1, 77, 200, 2, 64, False), None),
        ("k_streamed", ("s", 1, 77, 512, 2, 64, False), 256),
        ("xla_tier", ("x", 1, 77, 512, 2, 64, False), None),
    ])
    def test_other_geometries_concatenate_as_before(self, why, case,
                                                    block_k, monkeypatch):
        """Where the two-segment call cannot be taken the segments are
        concatenated for the one-segment dispatcher, and the answer is the
        same attention."""
        from comfyui_distributed_tpu.ops import attention as attn
        from comfyui_distributed_tpu.ops import flash_joint as fj
        from comfyui_distributed_tpu.ops.kernel_choice import KernelChoice

        monkeypatch.setattr(
            fj, "flash_joint_attention",
            lambda *a, **kw: pytest.fail("two-segment call taken"))
        _, B, T, N, H, D, _ = case
        txt, img = self._segments(case, jnp.float32, seed=5)
        choice = (KernelChoice("xla") if why == "xla_tier"
                  else KernelChoice("packed", 128, block_k or 640))
        t_out, i_out = attn.joint_attention(txt, img, H, choice=choice)
        ref = self._reference(txt, img, H)
        np.testing.assert_allclose(t_out, ref[:, :T], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(i_out, ref[:, T:], atol=2e-5, rtol=2e-5)

    def test_label_and_stated_cost(self, monkeypatch):
        """``select_kernel`` told the segments reports the two-segment
        tiles under the JOINT geometry's key, and the call states the
        one-segment call's algorithmic cost over the joint rows."""
        from comfyui_distributed_tpu.ops import attention as attn
        from comfyui_distributed_tpu.utils.flops import estimate_flops

        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        attn.reset_selections()
        choice = attn.select_kernel(4173, 4173, 24, 64, segments=(77, 4096))
        assert choice.tier == "packed" and choice.block_k == 4224
        assert attn.selection_summary() == (
            "h24.d64.q8192.kv8192.bf16="
            "packed:512+80/4096+128:k-resident")
        # a one-segment site of the same geometry keeps its label
        attn.reset_selections()
        attn.select_kernel(4173, 4173, 24, 64)
        assert attn.selection_summary().endswith(
            "=packed:464/4224:k-resident")

        case = ("cost", 2, 77, 1024, 4, 64, False)
        txt, img = self._segments(case, jnp.bfloat16)
        flops = estimate_flops(
            lambda: attn.joint_attention(
                txt, img, 4, choice=self._packed_choice(case)))
        assert flops == 4 * 2 * 4 * (77 + 1024) ** 2 * 64

    @pytest.mark.parametrize("shape,equations", [
        ((2, 4173, 24, 64, "bfloat16"), 95),     # SD3's joint rows, merged
        ((2, 4096, 10, 64, "bfloat16"), 76),     # SDXL's 64² self-attention
        ((1, 1024, 4, 128, "float32"), 16),
    ], ids=lambda x: str(x))
    def test_one_segment_call_traces_as_before(self, shape, equations):
        """PR 41 added a kernel beside ``_flash_mha_packed``; the
        one-segment call itself — SDXL's, WAN's, a single block's — must
        trace to the program it traced to (counts recorded at PR 40's
        tree)."""
        from comfyui_distributed_tpu.ops import flash_attention as fa

        B, N, H, D, dtype = shape
        q = jax.ShapeDtypeStruct((B, N, H * D), jnp.dtype(dtype))
        bq, bk = fa._packed_blocks(N, N, D, jnp.dtype(dtype).itemsize)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: fa._flash_mha_packed(
                q, k, v, num_heads=H, block_q=bq, block_k=bk,
                interpret=False))(q, q, q)
        assert _jaxpr_equations(jaxpr.jaxpr) == equations
