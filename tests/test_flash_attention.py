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


class TestNumerics:
    def test_block_aligned(self):
        q, k, v = rand_qkv(jax.random.key(0), Nq=256, Nk=256)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_ragged_lengths_masked(self):
        """Nq/Nk not multiples of the block sizes → padding is masked out."""
        q, k, v = rand_qkv(jax.random.key(1), Nq=100, Nk=77)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_reference(q, k, v)
        assert out.shape == (1, 100, 2, 64)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_multi_kv_blocks_accumulate(self):
        """Nk spanning several K blocks exercises the streaming-softmax
        carry (running max / denominator / accumulator rescale)."""
        q, k, v = rand_qkv(jax.random.key(2), Nq=128, Nk=512)
        out = flash_attention(q, k, v, block_k=128, interpret=True)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16_inputs(self):
        q, k, v = rand_qkv(jax.random.key(3), Nq=128, Nk=256,
                           dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_reference(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(np.float32), ref,
                                   atol=2e-2, rtol=2e-2)

    def test_extreme_logits_stable(self):
        """Large-magnitude logits must not overflow exp (running-max
        subtraction)."""
        q, k, v = rand_qkv(jax.random.key(4), Nq=128, Nk=256)
        q = q * 30.0
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_reference(q, k, v)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)

    def test_batch_and_heads(self):
        q, k, v = rand_qkv(jax.random.key(5), B=2, Nq=64, Nk=64, H=4, D=32)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_shape(self):
        """Cross attention: 77-token text context vs image queries."""
        q, k, v = rand_qkv(jax.random.key(6), Nq=256, Nk=77)
        out = flash_attention(q, k, v, interpret=True)
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
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.setenv("CDT_FLASH_ATTENTION", "0")
        assert not attn._flash_enabled()
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        assert attn._flash_enabled()

    def test_seq_length_gate(self, monkeypatch):
        """r04: with no explicit env the flash default is gated on q
        length — below CDT_FLASH_MIN_SEQ the XLA fused lowering wins on
        TPU (measured: scripts/mfu_probe.py, SDXL 1024² flash 0.1763
        s/fwd vs XLA 0.1677), so short sequences must resolve to False
        even on TPU. Off-TPU (this CPU host) both resolve False; the
        explicit flags override everything."""
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        assert attn._flash_min_seq() == 8192
        monkeypatch.setenv("CDT_FLASH_MIN_SEQ", "4096")
        assert attn._flash_min_seq() == 4096
        # short q: gated off regardless of platform
        assert not attn._flash_enabled(q_len=4095)
        # explicit force wins over the gate
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        assert attn._flash_enabled(q_len=64)
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "0")
        assert not attn._flash_enabled(q_len=1 << 20)

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
        (1, 600, 24, 128, 500),   # FLUX geometry: H*D=3072 exceeds the
                                  # native _PACKED_MAX_HD -> the ISSUE 8
                                  # shrink path serves it with smaller
                                  # [block, H*D] tiles (no classic
                                  # fallback; see TestPackedShrink)
    ])
    def test_packed_matches_bh(self, monkeypatch, shape):
        from comfyui_distributed_tpu.ops.flash_attention import flash_attention

        b, nq, h, d, nk = shape
        q = jax.random.normal(jax.random.key(0), (b, nq, h, d))
        k = jax.random.normal(jax.random.key(1), (b, nk, h, d))
        v = jax.random.normal(jax.random.key(2), (b, nk, h, d))
        monkeypatch.delenv("CDT_FLASH_LAYOUT", raising=False)
        a = flash_attention(q, k, v, interpret=True, layout="packed")
        b_ = flash_attention(q, k, v, interpret=True, layout="bh")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a), dense_reference(q, k, v),
                                   atol=5e-2, rtol=5e-2)


class TestShapeGate:
    """r04 final gate: on TPU (simulated here by patching jax.devices)
    the default picks flash per shape — packed-legal layouts engage at
    q ≥ 1024 with K ≥ 256 (measured crossover, docs/roofline.md finding
    1a), packed-illegal layouts keep the classic 8192 gate."""

    @pytest.fixture()
    def on_tpu(self, monkeypatch):
        import types

        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        monkeypatch.delenv("CDT_FLASH_MIN_SEQ", raising=False)
        monkeypatch.delenv("CDT_FLASH_MIN_SEQ_PACKED", raising=False)
        monkeypatch.delenv("CDT_FLASH_MIN_KV_PACKED", raising=False)
        monkeypatch.delenv("CDT_FLASH_LAYOUT", raising=False)
        monkeypatch.delenv("CDT_FLASH_BLOCK_Q", raising=False)
        monkeypatch.delenv("CDT_FLASH_BLOCK_K", raising=False)
        fake = types.SimpleNamespace(platform="tpu")
        monkeypatch.setattr(attn.jax, "devices", lambda *a: [fake])
        return attn

    def test_packed_legal_engages_at_sdxl_lengths(self, on_tpu):
        # SDXL self-attention: 4096 tokens, 10 heads × 64
        assert on_tpu._flash_enabled(q_len=4096, kv_len=4096,
                                     num_heads=10, head_dim=64)
        # the 32² block: 1024 tokens — exactly at the packed floor
        assert on_tpu._flash_enabled(q_len=1024, kv_len=1024,
                                     num_heads=20, head_dim=64)
        assert not on_tpu._flash_enabled(q_len=512, kv_len=512,
                                         num_heads=20, head_dim=64)

    def test_short_kv_cross_attention_stays_on_xla(self, on_tpu):
        # SDXL cross-attention: K = 77 text tokens → one mostly-padding
        # K block, measured behind XLA
        assert not on_tpu._flash_enabled(q_len=4096, kv_len=77,
                                         num_heads=10, head_dim=64)

    def test_packed_illegal_keeps_classic_gate(self, on_tpu):
        # FLUX: H·D = 3072 > _PACKED_MAX_HD → classic call, 8192 gate
        assert not on_tpu._flash_enabled(q_len=4608, kv_len=4608,
                                         num_heads=24, head_dim=128)
        assert on_tpu._flash_enabled(q_len=9000, kv_len=9000,
                                     num_heads=24, head_dim=128)

    def test_shape_free_call_keeps_classic_gate(self, on_tpu):
        # callers that pass only q_len (no head geometry) get the
        # classic 8192 threshold
        assert not on_tpu._flash_enabled(q_len=4096)
        assert on_tpu._flash_enabled(q_len=8192)

    def test_short_kv_long_q_falls_through_to_classic_gate(self, on_tpu):
        # packed-legal geometry whose KV floor fails must still reach
        # the classic bh gate at very long q (streamed-softmax memory
        # win), not silently drop flash entirely (r04 advisor finding)
        assert on_tpu._flash_enabled(q_len=16384, kv_len=77,
                                     num_heads=10, head_dim=64)
        assert not on_tpu._flash_enabled(q_len=4096, kv_len=77,
                                         num_heads=10, head_dim=64)

    def test_packed_layout_requires_lane_aligned_head_dim(self, monkeypatch):
        # H=128, D=16 passes the packed-width checks but would unroll a
        # 128-way head loop over 16-wide lane slices — excluded
        from comfyui_distributed_tpu.ops.flash_attention import _layout_packed

        monkeypatch.delenv("CDT_FLASH_LAYOUT", raising=False)
        assert not _layout_packed(128, 16)
        assert _layout_packed(10, 64)
        assert _layout_packed(16, 128)

    def test_malformed_gate_env_falls_back(self, on_tpu, monkeypatch):
        # an env typo must degrade to the default, not crash the gate
        monkeypatch.setenv("CDT_FLASH_MIN_SEQ_PACKED", "banana")
        assert on_tpu._flash_enabled(q_len=4096, kv_len=4096,
                                     num_heads=10, head_dim=64)

    def test_block_env_knobs_reach_kernel(self, monkeypatch):
        """CDT_FLASH_BLOCK_Q/K (r05 tuning knobs) change the kernel's
        block geometry without changing its math."""
        q, k, v = rand_qkv(jax.random.key(12), Nq=256, Nk=512)
        ref = dense_reference(q, k, v)
        monkeypatch.setenv("CDT_FLASH_BLOCK_Q", "128")
        monkeypatch.setenv("CDT_FLASH_BLOCK_K", "128")
        out = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_block_env_knobs_validated_at_parse(self, monkeypatch):
        """Non-positive or non-(8,128)-divisible block knobs raise a
        descriptive error at first use instead of letting pallas fail
        deep in Mosaic lowering (ISSUE 8 satellite; the old behavior
        silently fell back, hiding operator typos)."""
        q, k, v = rand_qkv(jax.random.key(12), Nq=256, Nk=512)
        monkeypatch.setenv("CDT_FLASH_BLOCK_Q", "0")
        with pytest.raises(ValueError, match="CDT_FLASH_BLOCK_Q"):
            flash_attention(q, k, v, interpret=True)
        monkeypatch.setenv("CDT_FLASH_BLOCK_Q", "100")   # not 8-divisible
        with pytest.raises(ValueError, match="multiple of 8"):
            flash_attention(q, k, v, interpret=True)
        monkeypatch.setenv("CDT_FLASH_BLOCK_Q", "256")
        monkeypatch.setenv("CDT_FLASH_BLOCK_K", "-64")
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, k, v, interpret=True)
        monkeypatch.setenv("CDT_FLASH_BLOCK_K", "banana")
        with pytest.raises(ValueError, match="not an integer"):
            flash_attention(q, k, v, interpret=True)
        # explicit arguments go through the same validation
        monkeypatch.delenv("CDT_FLASH_BLOCK_Q")
        monkeypatch.delenv("CDT_FLASH_BLOCK_K")
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, k, v, block_k=200, interpret=True)


class TestPackedShrink:
    """The VMEM working-set model and the block-shrinking legality path
    (ISSUE 8): geometries past the native packed ceiling get shrunken
    [block, H·D] tiles instead of the classic [B·H, N, D] fallback."""

    def test_vmem_model_matches_r05_wan_probe(self):
        """r05 measured: 1024 K-blocks at H·D=1536 blow the 16 MB scoped
        VMEM (25.09 MB), 512 K-blocks fit (docs/roofline.md). The model
        must reproduce that verdict."""
        from comfyui_distributed_tpu.ops.flash_attention import (
            _VMEM_BUDGET_BYTES, _packed_vmem_bytes)

        assert _packed_vmem_bytes(1536, 256, 1024, 2) > _VMEM_BUDGET_BYTES
        assert _packed_vmem_bytes(1536, 256, 512, 2) <= _VMEM_BUDGET_BYTES

    def test_flux_width_feasible_with_shrunk_blocks(self):
        from comfyui_distributed_tpu.ops.flash_attention import (
            _packed_feasible)

        # default blocks blow VMEM at H·D=3072; the shrink path lands on
        # a deterministic smaller pair instead of giving up
        assert _packed_feasible(24, 128, 256, 512, 2) == (256, 256)
        # f32 operands need a further shrink
        assert _packed_feasible(24, 128, 256, 512, 4) == (128, 128)
        # geometric illegality (lane-misaligned head dim) is still None
        assert _packed_feasible(128, 16) is None

    def test_explicit_packed_at_flux_width_runs_packed(self, monkeypatch):
        """Acceptance: the FLUX geometry no longer falls back to the
        classic call — an explicit packed request at H·D=3072 computes
        via the shrunk packed kernel and matches the dense reference."""
        from comfyui_distributed_tpu.ops import flash_attention as fa

        calls = []
        orig = fa._flash_mha_packed

        def spy(*args, **kw):
            calls.append((kw.get("block_q"), kw.get("block_k")))
            return orig(*args, **kw)

        monkeypatch.setattr(fa, "_flash_mha_packed", spy)
        q, k, v = rand_qkv(jax.random.key(20), B=1, Nq=600, Nk=500,
                           H=24, D=128)
        out = fa.flash_attention(q, k, v, interpret=True, layout="packed")
        np.testing.assert_allclose(np.asarray(out),
                                   dense_reference(q, k, v),
                                   atol=5e-2, rtol=5e-2)
        assert calls, "packed kernel was not used at H·D=3072"
        assert calls[0] == (128, 128)   # f32 shrink verdict


def fused_reference(x, wq, wk, wv, num_heads):
    B, N, C = x.shape
    D = wq.shape[-1] // num_heads
    q = (x @ wq).reshape(B, N, num_heads, D)
    k = (x @ wk).reshape(B, N, num_heads, D)
    v = (x @ wv).reshape(B, N, num_heads, D)
    return dense_reference(q, k, v)


def rand_fused(seed, B, N, C, HD=None):
    HD = C if HD is None else HD
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (B, N, C))
    scale = 1.0 / (C ** 0.5)
    return (x,) + tuple(jax.random.normal(k, (C, HD)) * scale
                        for k in ks[1:])


class TestFusedKernel:
    """Fused QKV-projection + attention tier: q/k/v are projected inside
    the flash grid from the block's input activations — parity against
    projection + dense attention across the geometry matrix
    (interpret mode, CPU)."""

    @pytest.mark.parametrize("name,B,N,C,H", [
        ("sdxl_self64", 2, 300, 640, 10),     # ragged N (padding edges)
        ("sdxl_self32", 1, 1024, 1280, 20),   # block-aligned
        ("flux_3072", 1, 600, 3072, 24),      # H·D=3072, ragged N
        ("tiny_ragged", 1, 77, 128, 2),       # N smaller than one block
    ])
    def test_matches_reference(self, name, B, N, C, H):
        from comfyui_distributed_tpu.ops.flash_attention import (
            fused_qkv_attention)

        x, wq, wk, wv = rand_fused(3, B, N, C)
        out = fused_qkv_attention(x, wq, wk, wv, H, interpret=True)
        ref = fused_reference(x, wq, wk, wv, H)
        assert out.shape == (B, N, H, C // H)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_wan_14k_token_shape(self):
        """≥14k tokens at WAN's head_dim=128 — the long-N regime the
        roofline names. Runs the emulated fused path (the same block
        schedule/masking as the kernel, XLA-compiled — the pallas
        interpreter's per-grid-step overhead is prohibitive at a
        57×29 grid); head count reduced to 2: the kernel unrolls heads
        identically regardless of H."""
        from comfyui_distributed_tpu.ops.flash_attention import (
            _fused_emulated)

        B, N, C, H = 1, 14464, 256, 2
        x, wq, wk, wv = rand_fused(5, B, N, C)
        out = _fused_emulated(x, wq, wk, wv, H, block_q=256, block_k=512)
        ref = fused_reference(x, wq, wk, wv, H)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_kernel_matches_emulated(self):
        """The pallas kernel and the plain-JAX emulation are the same
        block schedule — near-bitwise agreement, which is what makes
        emulated coverage of big shapes meaningful."""
        from comfyui_distributed_tpu.ops.flash_attention import (
            _fused_emulated, fused_qkv_attention)

        x, wq, wk, wv = rand_fused(7, 2, 300, 640)
        a = fused_qkv_attention(x, wq, wk, wv, 10, block_q=128,
                                block_k=128, interpret=True)
        b = _fused_emulated(x, wq, wk, wv, 10, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)

    def test_bf16_operands(self):
        from comfyui_distributed_tpu.ops.flash_attention import (
            fused_qkv_attention)

        x, wq, wk, wv = (t.astype(jnp.bfloat16)
                         for t in rand_fused(9, 1, 256, 640))
        out = fused_qkv_attention(x, wq, wk, wv, 10, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = fused_reference(x.astype(jnp.float32),
                              wq.astype(jnp.float32),
                              wk.astype(jnp.float32),
                              wv.astype(jnp.float32), 10)
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref), atol=5e-2, rtol=5e-2)

    def test_inside_shard_map(self):
        """Inside a dp shard_map trace the emulated path serves the
        fused tier (the pallas interpreter can't — same check_vma
        constraint as the plain kernel)."""
        from jax.sharding import PartitionSpec as P

        from comfyui_distributed_tpu.ops.flash_attention import (
            fused_qkv_attention)
        from comfyui_distributed_tpu.parallel.mesh import build_mesh

        mesh = build_mesh({"dp": 8})
        x, wq, wk, wv = rand_fused(11, 8, 64, 128)

        def per_shard(x, wq, wk, wv):
            return fused_qkv_attention(x, wq, wk, wv, 2, interpret=True)

        f = jax.jit(shard_map(
            per_shard, mesh=mesh,
            in_specs=(P("dp"), P(), P(), P()),
            out_specs=P("dp")))
        out = f(x, wq, wk, wv)
        ref = fused_reference(x, wq, wk, wv, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_split_qkv_weight(self):
        from comfyui_distributed_tpu.ops.flash_attention import (
            fused_qkv_attention, split_qkv_weight)

        C = 128
        w = jax.random.normal(jax.random.key(13), (C, 3 * C)) / C ** 0.5
        wq, wk, wv = split_qkv_weight(w)
        assert wq.shape == wk.shape == wv.shape == (C, C)
        x = jax.random.normal(jax.random.key(14), (1, 200, C))
        out = fused_qkv_attention(x, wq, wk, wv, 2, interpret=True)
        ref = fused_reference(x, wq, wk, wv, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_shape_validation(self):
        from comfyui_distributed_tpu.ops.flash_attention import (
            fused_qkv_attention)

        x, wq, wk, wv = rand_fused(15, 1, 64, 128)
        with pytest.raises(ValueError, match="num_heads"):
            fused_qkv_attention(x, wq, wk, wv, 3, interpret=True)
        with pytest.raises(ValueError, match=r"\[C, H·D\]"):
            fused_qkv_attention(x, wq[:64], wk, wv, 2, interpret=True)


class TestFusedModelSite:
    """The SDXL UNet self-attention site (models/layers.py Attention)
    takes the fused path when the dispatcher picks it, with the same
    params either way — checkpoints can't tell the branches apart."""

    def _table_with_fused(self, h, d, q, kv):
        from comfyui_distributed_tpu.ops import autotune

        autotune.reset_default_table()
        t = autotune.default_table()
        # dtype must match the module's (f32 here) — the table keys on it
        t.record(autotune.GeometryKey.from_shape(h, d, q, kv, "float32"),
                 autotune.KernelChoice("fused", 128, 128, source="sweep"),
                 save=False)
        return t

    def test_fused_branch_matches_dense_branch(self, monkeypatch):
        import flax.linen as nn  # noqa: F401

        from comfyui_distributed_tpu.models.layers import Attention
        from comfyui_distributed_tpu.ops import attention as attn

        H, D, N, C = 2, 64, 256, 128
        x = jax.random.normal(jax.random.key(16), (1, N, C))
        module = Attention(num_heads=H, head_dim=D, dtype=jnp.float32)
        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        params = module.init(jax.random.key(17), x)
        dense_out = module.apply(params, x)
        # force the fused tier (table entry + forced flash so the CPU
        # platform gate doesn't veto it)
        self._table_with_fused(H, D, N, N)
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        attn.reset_selections()
        fused_out = module.apply(params, x)
        assert "to_q" in params["params"]
        np.testing.assert_allclose(np.asarray(fused_out),
                                   np.asarray(dense_out),
                                   atol=2e-4, rtol=2e-4)
        assert any(d.startswith("fused")
                   for d in attn.selection_summary().split(",")
                   for g, _, d in [d.partition("=")])

    def test_infeasible_real_width_degrades_to_dense(self, monkeypatch):
        """The table validates fused feasibility assuming C == H·D; a
        site whose REAL channel width is lane-misaligned must degrade to
        the dense path instead of raising mid-forward (review finding)."""
        from comfyui_distributed_tpu.models.layers import Attention

        H, D, N, C = 2, 64, 256, 96          # C % 128 != 0 → fused illegal
        x = jax.random.normal(jax.random.key(21), (1, N, C))
        self._table_with_fused(H, D, N, N)
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        module = Attention(num_heads=H, head_dim=D, dtype=jnp.float32)
        params = module.init(jax.random.key(22), x)
        out = module.apply(params, x)
        assert out.shape == (1, N, C)

    def test_cross_attention_never_fuses(self, monkeypatch):
        from comfyui_distributed_tpu.models.layers import Attention

        H, D, N, C, M = 2, 64, 256, 128, 77
        x = jax.random.normal(jax.random.key(18), (1, N, C))
        ctx = jax.random.normal(jax.random.key(19), (1, M, C))
        self._table_with_fused(H, D, N, M)
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        module = Attention(num_heads=H, head_dim=D, dtype=jnp.float32)
        params = module.init(jax.random.key(20), x, ctx)
        out = module.apply(params, x, ctx)   # downgrades, must not crash
        assert out.shape == (1, N, C)
