"""Attention kernel selection (``ops/attention.select_kernel`` and the one
policy behind it, ``ops/kernel_choice.py``'s value types): the geometry
label, the dispatcher's precedence (kill switch > platform > the policy >
the flash upgrade), every selection the cells and the zoo trace pinned, and
that no file can outrank the policy. Fast — no model builds; tier-1. The
only pallas execution is the four tiny interpret-mode cases of
``TestPackedKernelSmoke`` at the end."""

import json
import types

import pytest

from comfyui_distributed_tpu.ops import kernel_choice
from comfyui_distributed_tpu.ops.attention import policy_choice
from comfyui_distributed_tpu.ops.kernel_choice import GeometryKey


class TestGeometryKey:
    def test_bucketing(self):
        assert kernel_choice.seq_bucket(77) == 128
        assert kernel_choice.seq_bucket(128) == 128
        assert kernel_choice.seq_bucket(129) == 256
        assert kernel_choice.seq_bucket(4096) == 4096
        assert kernel_choice.seq_bucket(14040) == 16384

    def test_key_str_is_the_bucketed_label(self):
        k = GeometryKey.from_shape(12, 128, 14040, 512, "bfloat16")
        assert k.q_bucket == 16384 and k.kv_bucket == 512
        assert k.key_str() == "h12.d128.q16384.kv512.bf16"
        assert k.shard(2).key_str() == "h6.d128.q16384.kv512.bf16"
        assert k.shard(5) == k                  # 12 heads: no 5-way split

    def test_dtype_names(self):
        import jax.numpy as jnp

        assert kernel_choice.dtype_name(jnp.bfloat16) == "bf16"
        assert kernel_choice.dtype_name("float32") == "f32"
        assert kernel_choice.dtype_name("bf16") == "bf16"


class TestPolicy:
    """``policy_choice`` — the one rule — asked directly, platform aside."""

    def test_flux_geometry_does_not_fall_back_to_classic(self):
        """Acceptance: H·D=3072 runs packed, not the classic bh call."""
        assert policy_choice(4608, 4608, 24, 128).tier == "packed"

    def test_dry_policy_short_sequences_stay_xla(self):
        assert policy_choice(512, 512, 10, 64).tier == "xla"

    def test_dry_policy_flux_width_gets_packed_with_the_shapes_blocks(self):
        choice = policy_choice(8192, 8192, 24, 128)
        assert choice.tier == "packed"
        # no blocks in the policy's answer: select_kernel derives them
        # from the lengths the call meets, not from a bucket
        assert (choice.block_q, choice.block_k) == (None, None)


@pytest.fixture()
def on_tpu(monkeypatch):
    """ops.attention with the platform reading ``tpu`` and no kernel env
    set: what ``select_kernel`` answers on the chip."""
    from comfyui_distributed_tpu.ops import attention as attn

    monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
    fake = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(attn.jax, "devices", lambda *a: [fake])
    attn.reset_selections()
    return attn


class TestDispatcherPrecedence:
    """select_kernel: CDT_FLASH_ATTENTION=0 > the platform > the one
    policy; ``=1`` and ``prefer_flash`` outrank the policy's ``xla``."""

    def test_explicit_flag_beats_policy(self, on_tpu, monkeypatch):
        assert on_tpu.policy_choice(4096, 4096, 10, 64).tier == "packed"
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "0")
        choice = on_tpu.select_kernel(4096, 4096, 10, 64)
        assert (choice.tier, choice.source) == ("xla", "env")

    def test_explicit_force_beats_policy_xla(self, on_tpu, monkeypatch):
        """CDT_FLASH_ATTENTION=1 promises flash; the policy's 'xla' must
        yield to it (precedence says explicit env beats the rule both
        ways, not just for =0)."""
        assert on_tpu.policy_choice(4096, 128, 10, 64).tier == "xla"
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        assert on_tpu.select_kernel(4096, 128, 10, 64).tier != "xla"

    def test_itemsize_of_handles_scalar_types(self):
        import jax.numpy as jnp

        assert kernel_choice.itemsize_of(jnp.float32) == 4
        assert kernel_choice.itemsize_of(jnp.bfloat16) == 2
        assert kernel_choice.itemsize_of("f32") == 4
        assert kernel_choice.itemsize_of("bfloat16") == 2

    def test_prefer_flash_ignores_policy_xla(self, on_tpu):
        """The memory-constrained caller's guarantee survives the
        policy's speed-optimized floors."""
        assert on_tpu.policy_choice(4608, 4608, 5, 64).tier == "xla"
        assert on_tpu.select_kernel(4608, 4608, 5, 64).tier == "xla"
        choice = on_tpu.select_kernel(4608, 4608, 5, 64, prefer_flash=True)
        assert choice.tier == "bh"

    def test_off_tpu_defaults_to_xla(self, monkeypatch):
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        choice = attn.select_kernel(4096, 4096, 10, 64)
        assert choice.tier == "xla"

    def test_selection_telemetry_counter(self, on_tpu):
        from comfyui_distributed_tpu.telemetry import metrics as tm

        key = GeometryKey.from_shape(10, 64, 4096, 4096)
        on_tpu.reset_selections()
        before = {tuple(sorted(lbl.items())): snap.get("value", 0)
                  for lbl, snap in tm.ATTN_KERNEL_SELECTED.series()}
        on_tpu.select_kernel(4096, 4096, 10, 64)
        on_tpu.select_kernel(4096, 4096, 10, 64)   # dedup: one increment
        series = {tuple(sorted(lbl.items())): snap.get("value", 0)
                  for lbl, snap in tm.ATTN_KERNEL_SELECTED.series()}
        lbl = tuple(sorted({"tier": "packed", "geometry": key.key_str(),
                            "blocks": "512/4096:k-resident"}.items()))
        assert series.get(lbl, 0) - before.get(lbl, 0) == 1
        assert key.key_str() in on_tpu.selection_summary()

# (site, heads, head_dim, q_len, kv_len, dtype, prefer_flash, tp)
# → (tier, block_q, block_k), recorded from PR 27's tree (the parent of the
# PR that merged the two rule sets into one policy) with the platform
# reading ``tpu``: every site the benchmark's cells trace, a tp=2 shard of
# each, every geometry of the model zoo, the memory-constrained callers,
# and geometries on both sides of each floor. A row changes only with the
# policy line that a PR means to change: PR 55 deleted the tuning table
# that stood ahead of the policy and every row held, but for
# ``policy.exact_length_below_floor``, whose 1000 tokens are past the floor
# that PR's chip reading moved to 832.
PINNED_SELECTIONS = [
    ("solo30.self64", 10, 64, 4096, 4096, "bf16", False, 1,
     ("packed", 512, 4096)),
    ("solo30.self32", 20, 64, 1024, 1024, "bf16", False, 1,
     ("packed", 512, 1024)),
    ("solo30.cross64", 10, 64, 4096, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("solo30.cross32", 20, 64, 1024, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("cells.text_encoder", 12, 64, 77, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("solo28.joint", 24, 64, 4173, 4173, "bf16", False, 1,
     ("packed", 464, 4224)),
    ("solo28.joint.tp2", 24, 64, 4173, 4173, "bf16", False, 2,
     ("packed", 464, 4224)),
    ("solo30.self64.tp2", 10, 64, 4096, 4096, "bf16", False, 2,
     ("xla", None, None)),
    ("solo30.self32.tp2", 20, 64, 1024, 1024, "bf16", False, 2,
     ("packed", 512, 1024)),
    ("zoo.sdxl_self64", 10, 64, 4096, 4096, "bf16", False, 1,
     ("packed", 512, 4096)),
    ("zoo.sdxl_self32", 20, 64, 1024, 1024, "bf16", False, 1,
     ("packed", 512, 1024)),
    ("zoo.sdxl_cross64", 10, 64, 4096, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("zoo.sdxl_cross32", 20, 64, 1024, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("zoo.flux_joint", 24, 128, 4608, 4608, "bf16", False, 1,
     ("packed", 512, 4608)),
    ("zoo.wan_self", 12, 128, 14040, 14040, "bf16", False, 1,
     ("packed", 512, 14080)),
    ("zoo.wan_cross", 12, 128, 14040, 512, "bf16", False, 1,
     ("packed", 512, 512)),
    ("prefer.flux_joint", 24, 128, 4608, 4608, "bf16", True, 1,
     ("packed", 512, 4608)),
    ("prefer.over_xla_row", 10, 64, 4096, 77, "bf16", True, 1,
     ("bh", None, None)),
    ("prefer.short_untabled", 8, 64, 512, 512, "bf16", True, 1,
     ("bh", None, None)),
    ("prefer.packed_illegal", 5, 64, 4608, 4608, "bf16", True, 1,
     ("bh", None, None)),
    ("policy.exact_length_below_floor", 16, 64, 830, 830, "bf16", False, 1,
     ("xla", None, None)),
    ("policy.at_floor", 16, 64, 1024, 256, "bf16", False, 1,
     ("packed", 512, 256)),
    ("policy.short_kv", 16, 64, 2048, 255, "bf16", False, 1,
     ("xla", None, None)),
    ("policy.packed_illegal_mid", 5, 64, 4608, 4608, "bf16", False, 1,
     ("xla", None, None)),
    ("policy.packed_illegal_long", 5, 64, 9000, 9000, "bf16", False, 1,
     ("bh", None, None)),
    ("policy.short_kv_long_q", 10, 64, 16384, 77, "bf16", False, 1,
     ("bh", None, None)),
    ("policy.f32_joint", 24, 64, 4173, 4173, "f32", False, 1,
     ("packed", 464, 4224)),
    ("policy.d128_streams", 16, 128, 40000, 40000, "bf16", False, 1,
     ("packed", 512, 20096)),
    # PR 55: the zoo's real resolutions that fall INSIDE a label's bucket,
    # where the table's bucketed rows answered for lengths they were not
    # written at. SDXL's 32² level (h20) under 1024 tokens carries what the
    # chip reading decided: packed from 832 up, xla below (the parent's
    # table said packed down to 513, its policy xla up to 1023).
    ("sdxl768.self64", 10, 64, 2304, 2304, "bf16", False, 1,
     ("packed", 464, 2304)),
    ("sdxl768.self32", 20, 64, 576, 576, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl768.cross64", 10, 64, 2304, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl768.cross32", 20, 64, 576, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl1152x896.self64", 10, 64, 4032, 4032, "bf16", False, 1,
     ("packed", 512, 4096)),
    ("sdxl1152x896.self32", 20, 64, 1008, 1008, "bf16", False, 1,
     ("packed", 512, 1024)),
    ("sdxl1152x896.cross64", 10, 64, 4032, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl1152x896.cross32", 20, 64, 1008, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl1216x832.self64", 10, 64, 3952, 3952, "bf16", False, 1,
     ("packed", 496, 3968)),
    ("sdxl1216x832.self32", 20, 64, 988, 988, "bf16", False, 1,
     ("packed", 496, 1024)),
    ("sdxl1024x832.self32.at_floor", 20, 64, 832, 832, "bf16", False, 1,
     ("packed", 416, 896)),
    ("sdxl1024x800.self32.below_floor", 20, 64, 800, 800, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl512.self64", 10, 64, 1024, 1024, "bf16", False, 1,
     ("packed", 512, 1024)),
    ("sdxl512.self32", 20, 64, 256, 256, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl512.cross32", 20, 64, 256, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("sdxl1536.self64", 10, 64, 9216, 9216, "bf16", False, 1,
     ("packed", 512, 9216)),
    ("sdxl1536.self32", 20, 64, 2304, 2304, "bf16", False, 1,
     ("packed", 464, 2304)),
    ("sdxl1536.cross64", 10, 64, 9216, 77, "bf16", False, 1,
     ("bh", None, None)),
    ("sdxl1536.cross32", 20, 64, 2304, 77, "bf16", False, 1,
     ("xla", None, None)),
    ("sd3_768.joint", 24, 64, 2381, 2381, "bf16", False, 1,
     ("packed", 480, 2432)),
    ("sd3_512.joint", 24, 64, 1101, 1101, "bf16", False, 1,
     ("packed", 368, 1152)),
    ("sd3_1152x896.joint", 24, 64, 4109, 4109, "bf16", False, 1,
     ("packed", 464, 4224)),
    ("flux768.joint", 24, 128, 2816, 2816, "bf16", False, 1,
     ("packed", 480, 2816)),
]


@pytest.mark.parametrize("case", PINNED_SELECTIONS, ids=lambda c: c[0])
def test_selection_pinned(on_tpu, case):
    _, heads, head_dim, q_len, kv_len, dtype, prefer, tp, want = case
    with on_tpu.tp_shard_scope(tp):
        choice = on_tpu.select_kernel(q_len, kv_len, heads, head_dim,
                                      dtype=dtype, prefer_flash=prefer)
    assert (choice.tier, choice.block_q, choice.block_k) == want


@pytest.mark.parametrize("cross", [False, True], ids=["self", "cross"])
def test_attention_site_asks_once(monkeypatch, cross):
    """``models/layers.Attention`` runs the choice the site asked for:
    ``full_attention`` does not look it up again."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.layers import Attention
    from comfyui_distributed_tpu.ops import attention as attn

    calls = []
    real = attn.select_kernel

    def spy(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(attn, "select_kernel", spy)
    x = jnp.ones((1, 16, 128))
    ctx = (jnp.ones((1, 7, 128)),) if cross else ()
    module = Attention(num_heads=2, head_dim=64, dtype=jnp.float32)
    out = jax.eval_shape(
        lambda: module.init_with_output(jax.random.key(0), x, *ctx)[0])
    assert out.shape == x.shape
    assert len(calls) == 1
    assert calls[0][0] == (16, 7 if cross else 16, 2, 64)
    assert set(calls[0][1]) == {"dtype"}


def test_a_stray_overlay_beside_the_compile_cache_changes_nothing(
        on_tpu, tmp_path, monkeypatch):
    """Up to PR 54 a well-formed ``attn_tuning.json`` beside the compile
    cache outranked the policy. Nothing reads such a file now: the answer
    is the policy's whatever lies there."""
    import os

    from comfyui_distributed_tpu.utils import compile_cache

    for var in [v for v in os.environ if v.startswith("CDT_")]:
        monkeypatch.delenv(var)         # no knob of any name points elsewhere
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir_default() == str(tmp_path)
    (tmp_path / "attn_tuning.json").write_text(json.dumps({
        "version": 1,
        "entries": {"h10.d64.q4096.kv4096.bf16": {"tier": "xla"}}}))
    choice = on_tpu.select_kernel(4096, 4096, 10, 64)
    assert (choice.tier, choice.block_q, choice.block_k) == \
        ("packed", 512, 4096)
    assert choice.source == "default"


def test_select_kernel_opens_no_file_and_reads_one_variable(
        on_tpu, monkeypatch):
    """The whole of what a selection may consult: its arguments, the tp
    scope, the platform and ``CDT_FLASH_ATTENTION``."""
    import builtins
    import io
    import os

    def refuse(*a, **kw):
        raise AssertionError(f"select_kernel opened a file: {a}")

    read = []
    real_get = os.environ.get

    def spy(name, *default):
        read.append(name)
        return real_get(name, *default)

    sites = ((4096, 4096, 10, 64), (1024, 77, 20, 64), (4173, 4173, 24, 64))
    for args in sites:          # imports done, each site's log line out
        on_tpu.select_kernel(*args)
    monkeypatch.setattr(builtins, "open", refuse)
    monkeypatch.setattr(io, "open", refuse)
    monkeypatch.setattr(os, "open", refuse)
    monkeypatch.setattr(os.environ, "get", spy, raising=False)
    tiers = [on_tpu.select_kernel(*args).tier for args in sites]
    monkeypatch.undo()
    assert tiers == ["packed", "xla", "packed"]
    assert set(read) == {"CDT_FLASH_ATTENTION"}, read


class TestPackedKernelSmoke:
    """The packed kernel's mathematics in the smoke tier (the full matrix
    is ``tests/test_flash_attention.py``, marked slow): interpret mode,
    tiny ragged shapes, both head-group kinds, K resident and streamed,
    and SDXL's 64² site as it runs (an odd count of D=64 head groups
    behind plain projections)."""

    @pytest.mark.parametrize("case", [
        ("d64.resident", 2, 100, 77, 4, 64, None),
        ("d64.streamed", 1, 100, 300, 2, 64, 128),
        ("d128.resident", 1, 72, 200, 1, 128, None),
        ("d128.streamed", 1, 72, 200, 3, 128, 128),
    ], ids=lambda c: c[0])
    def test_matches_xla_reference(self, case):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from comfyui_distributed_tpu.ops import flash_attention as fa

        _, B, Nq, Nk, H, D, bk = case
        kq, kk, kv = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(kq, (B, Nq, H, D), jnp.float32)
        k = jax.random.normal(kk, (B, Nk, H, D), jnp.float32)
        v = jax.random.normal(kv, (B, Nk, H, D), jnp.float32)
        out = fa.flash_attention(q, k, v, block_k=bk, interpret=True,
                                 layout="packed")
        np.testing.assert_allclose(
            out, jax.nn.dot_product_attention(q, k, v),
            atol=2e-5, rtol=2e-5)

    def test_sdxl_self64_site_through_attention_module(self, monkeypatch):
        """H=10, D=64 (five 128-lane groups) through ``layers.Attention``
        with the kernels forced on, at a ragged length just past 1024:
        the site projects with ``nn.Dense`` and runs
        the packed call, against XLA attention on the same parameters."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from comfyui_distributed_tpu.models.layers import Attention
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        attn.reset_selections()
        module = Attention(num_heads=10, head_dim=64, dtype=jnp.float32)
        x = jax.random.normal(jax.random.key(5), (1, 1030, 640), jnp.float32)
        params = module.init(jax.random.key(6), x)
        assert set(params["params"]) == {"to_q", "to_k", "to_v", "to_out"}
        out = module.apply(params, x)
        assert "h10.d64.q2048.kv2048.f32=packed:344/1152:k-resident" \
            in attn.selection_summary()

        monkeypatch.setenv("CDT_FLASH_ATTENTION", "0")
        np.testing.assert_allclose(out, module.apply(params, x),
                                   atol=2e-5, rtol=2e-5)
