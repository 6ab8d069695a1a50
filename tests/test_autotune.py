"""Attention autotuner (ops/autotune.py): table persistence + merge,
shipped-table legality, deterministic sweeps, and dispatcher precedence
(kill switch > table row > the one policy). Fast — no model builds;
tier-1. The only pallas execution is the four tiny interpret-mode cases
of ``TestPackedKernelSmoke`` at the end."""

import json
import types

import pytest

from comfyui_distributed_tpu.ops import autotune
from comfyui_distributed_tpu.ops.autotune import (
    GeometryKey, KernelChoice, TuningTable)


def geom(h=10, d=64, q=4096, kv=4096, dtype="bf16"):
    return GeometryKey(num_heads=h, head_dim=d, q_bucket=q, kv_bucket=kv,
                       dtype=dtype)


class TestGeometryKey:
    def test_bucketing(self):
        assert autotune.seq_bucket(77) == 128
        assert autotune.seq_bucket(128) == 128
        assert autotune.seq_bucket(129) == 256
        assert autotune.seq_bucket(4096) == 4096
        assert autotune.seq_bucket(14040) == 16384

    def test_key_str_round_trip(self):
        k = GeometryKey.from_shape(12, 128, 14040, 512, "bfloat16")
        assert k.q_bucket == 16384 and k.kv_bucket == 512
        assert GeometryKey.from_key_str(k.key_str()) == k

    def test_dtype_names(self):
        import jax.numpy as jnp

        assert autotune.dtype_name(jnp.bfloat16) == "bf16"
        assert autotune.dtype_name("float32") == "f32"
        assert autotune.dtype_name("bf16") == "bf16"

    def test_malformed_key_str_raises(self):
        with pytest.raises(ValueError, match="malformed"):
            GeometryKey.from_key_str("not-a-key")


class TestTableRoundTrip:
    def test_record_save_load(self, tmp_path):
        path = tmp_path / "table.json"
        t = TuningTable(path=path, shipped=False)
        t.record(geom(), KernelChoice("packed", 256, 512, source="sweep"))
        t2 = TuningTable(path=path, shipped=False)
        got = t2.get(geom())
        assert got is not None
        assert (got.tier, got.block_q, got.block_k) == ("packed", 256, 512)

    def test_atomic_merge_across_writers(self, tmp_path):
        """Two processes sweeping different geometries into one file must
        union, not clobber (the shape-catalog contract)."""
        path = tmp_path / "table.json"
        a = TuningTable(path=path, shipped=False)
        b = TuningTable(path=path, shipped=False)
        a.record(geom(h=10), KernelChoice("packed", 256, 512, source="sweep"))
        b.record(geom(h=20, q=1024, kv=1024),
                 KernelChoice("xla", source="sweep"))
        merged = TuningTable(path=path, shipped=False)
        assert merged.get(geom(h=10)) is not None
        assert merged.get(geom(h=20, q=1024, kv=1024)) is not None

    def test_corrupt_file_degrades_to_empty(self, tmp_path):
        path = tmp_path / "table.json"
        path.write_text("{not json")
        t = TuningTable(path=path, shipped=False)
        assert len(t) == 0
        # and the next save heals the file
        t.record(geom(), KernelChoice("packed", 256, 512, source="sweep"))
        assert json.loads(path.read_text())["entries"]

    def test_malformed_entries_skipped(self, tmp_path):
        path = tmp_path / "table.json"
        path.write_text(json.dumps({
            "version": 1,
            "entries": {
                "h10.d64.q4096.kv4096.bf16": {"tier": "packed",
                                              "block_q": 256,
                                              "block_k": 512},
                "garbage": {"tier": "packed"},
                "h2.d64.q128.kv128.bf16": {"tier": "warp-drive"},
                # a row a local overlay kept from before the fused QKV
                # tier went (PR 42): skipped, not crashed on
                "h10.d64.q1024.kv1024.bf16": {"tier": "fused",
                                              "block_q": 256,
                                              "block_k": 512},
            }}))
        t = TuningTable(path=path, shipped=False)
        assert len(t) == 1

    def test_local_overrides_shipped(self, tmp_path):
        t = TuningTable(path=tmp_path / "t.json", shipped=True)
        shipped_geom = GeometryKey.from_shape(24, 128, 4608, 4608)
        assert t.get(shipped_geom) is not None          # shipped FLUX entry
        t.record(shipped_geom, KernelChoice("bh", 256, 512, source="sweep"))
        assert t.get(shipped_geom).tier == "bh"


class TestShippedTable:
    """The resolved model-zoo table that ships in-repo must parse and
    every entry must pass the legality checks — a bad bake fails here,
    not in Mosaic lowering on a serving host."""

    def test_parses_and_covers_the_zoo(self):
        t = TuningTable(shipped=True, path="/nonexistent/none.json",
                        autoload=True)
        entries = t.entries()
        assert entries, "shipped table is empty"
        zoo = autotune.model_zoo_geometries()
        for name, key in zoo.items():
            assert t.get(key) is not None, f"zoo geometry {name} untuned"

    def test_every_entry_passes_legality(self):
        t = TuningTable(shipped=True, path="/nonexistent/none.json")
        for key, choice in t.entries().items():
            errors = autotune.validate_entry(key, choice)
            assert not errors, f"{key.key_str()}: {errors}"

    def test_flux_geometry_does_not_fall_back_to_classic(self):
        """Acceptance: H·D=3072 runs packed, not the classic bh call."""
        t = TuningTable(shipped=True, path="/nonexistent/none.json")
        choice = t.get(GeometryKey.from_shape(24, 128, 4608, 4608))
        assert choice.tier == "packed"

    def test_validate_entry_catches_vmem_blowout(self):
        # a packed K tile is one head group wide: 1024 rows fit where the
        # full-width tile's did not; a 4096-row q block against all of
        # WAN's K does not
        assert not autotune.validate_entry(
            geom(h=12, d=128, q=16384, kv=16384),
            KernelChoice("packed", 256, 1024))
        errors = autotune.validate_entry(
            geom(h=12, d=128, q=16384, kv=16384),
            KernelChoice("packed", 4096, 16384))
        assert errors and "VMEM" in errors[0]

    def test_validate_entry_catches_bad_blocks(self):
        errors = autotune.validate_entry(
            geom(), KernelChoice("packed", 100, 512))
        assert errors and "multiple of 8" in errors[0]


class TestSweep:
    def test_dry_sweep_deterministic(self):
        k = geom(h=12, d=128, q=16384, kv=16384)
        a = autotune.sweep_geometry(k, mode="dry")
        b = autotune.sweep_geometry(k, mode="dry")
        assert a.choice == b.choice
        assert a.choice.tier == "packed"

    def test_dry_policy_short_sequences_stay_xla(self):
        e = autotune.sweep_geometry(geom(q=512, kv=512), mode="dry")
        assert e.choice.tier == "xla"

    def test_dry_policy_flux_width_gets_packed_with_the_shapes_blocks(self):
        e = autotune.sweep_geometry(
            geom(h=24, d=128, q=8192, kv=8192), mode="dry")
        assert e.choice.tier == "packed"
        # no blocks in the row: the call derives them from the lengths it
        # meets, not from the bucket
        assert (e.choice.block_q, e.choice.block_k) == (None, None)
        assert "block_q" not in e.choice.to_dict()

    def test_candidates_deterministic_and_legal(self):
        k = geom(h=24, d=128, q=8192, kv=8192)
        cands = autotune.candidates_for(k)
        assert cands == autotune.candidates_for(k)
        assert cands[-1].tier == "xla"
        for c in cands:
            assert not autotune.validate_entry(k, c)

    def test_ensure_tuned_records_and_caches(self, tmp_path):
        t = TuningTable(path=tmp_path / "t.json", shipped=False)
        keys = [geom(), geom(h=20, q=1024, kv=1024)]
        first = autotune.ensure_tuned(keys, table=t, mode="dry")
        assert all(e.outcome == "dry" for e in first)
        again = autotune.ensure_tuned(keys, table=t, mode="dry")
        assert all(e.outcome == "cached" for e in again)
        # persisted: a fresh instance sees both entries
        t2 = TuningTable(path=tmp_path / "t.json", shipped=False)
        assert all(t2.get(k) is not None for k in keys)


@pytest.fixture()
def on_tpu(monkeypatch):
    """ops.attention with the platform reading ``tpu`` and no kernel env
    set: what ``select_kernel`` answers on the chip."""
    from comfyui_distributed_tpu.ops import attention as attn

    for var in ("CDT_FLASH_ATTENTION", "CDT_ATTN_TUNE"):
        monkeypatch.delenv(var, raising=False)
    fake = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(attn.jax, "devices", lambda *a: [fake])
    attn.reset_selections()
    return attn


class TestDispatcherPrecedence:
    """select_kernel: CDT_FLASH_ATTENTION=0 > tuning-table row > the one
    policy; deterministic given a table."""

    def table_with(self, key, choice):
        autotune.reset_default_table()
        t = autotune.default_table()
        t.record(key, choice, save=False)
        return t

    def test_table_beats_policy(self, on_tpu):
        assert on_tpu.policy_choice(4096, 4096, 10, 64).tier == "packed"
        key = GeometryKey.from_shape(10, 64, 4096, 4096)
        self.table_with(key, KernelChoice("bh", 128, 256, source="sweep"))
        choice = on_tpu.select_kernel(4096, 4096, 10, 64)
        assert (choice.tier, choice.block_q, choice.block_k) == \
            ("bh", 128, 256)

    def test_explicit_flag_beats_table(self, on_tpu, monkeypatch):
        key = GeometryKey.from_shape(10, 64, 4096, 4096)
        self.table_with(key, KernelChoice("packed", 256, 512,
                                          source="sweep"))
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "0")
        assert on_tpu.select_kernel(4096, 4096, 10, 64).tier == "xla"

    def test_deterministic_given_table(self, on_tpu):
        key = GeometryKey.from_shape(12, 128, 14040, 14040)
        self.table_with(key, KernelChoice("packed", 256, 512,
                                          source="sweep"))
        a = on_tpu.select_kernel(14040, 14040, 12, 128)
        b = on_tpu.select_kernel(14040, 14040, 12, 128)
        assert a == b
        assert (a.tier, a.block_q, a.block_k) == ("packed", 256, 512)

    def test_explicit_force_beats_table_xla(self, on_tpu, monkeypatch):
        """CDT_FLASH_ATTENTION=1 promises flash; a table 'xla' entry
        must yield to it (review finding: precedence says explicit env
        beats the table both ways, not just for =0)."""
        key = GeometryKey.from_shape(10, 64, 4096, 128)
        self.table_with(key, KernelChoice("xla", source="sweep"))
        monkeypatch.setenv("CDT_FLASH_ATTENTION", "1")
        assert on_tpu.select_kernel(4096, 128, 10, 64).tier != "xla"

    def test_itemsize_of_handles_scalar_types(self):
        import jax.numpy as jnp

        assert autotune.itemsize_of(jnp.float32) == 4
        assert autotune.itemsize_of(jnp.bfloat16) == 2
        assert autotune.itemsize_of("f32") == 4
        assert autotune.itemsize_of("bfloat16") == 2

    def test_prefer_flash_ignores_table_xla(self, on_tpu):
        """The memory-constrained caller's guarantee survives a
        speed-optimized table entry."""
        key = GeometryKey.from_shape(24, 128, 4608, 4608)
        self.table_with(key, KernelChoice("xla", source="sweep"))
        choice = on_tpu.select_kernel(4608, 4608, 24, 128,
                                      prefer_flash=True)
        assert choice.tier != "xla"

    def test_off_tpu_defaults_to_xla(self, monkeypatch):
        from comfyui_distributed_tpu.ops import attention as attn

        monkeypatch.delenv("CDT_FLASH_ATTENTION", raising=False)
        choice = attn.select_kernel(4096, 4096, 10, 64)
        assert choice.tier == "xla"

    def test_selection_telemetry_counter(self, on_tpu):
        from comfyui_distributed_tpu.telemetry import metrics as tm

        key = GeometryKey.from_shape(10, 64, 4096, 4096)
        self.table_with(key, KernelChoice("packed", 256, 512,
                                          source="sweep"))
        on_tpu.reset_selections()
        before = {tuple(sorted(lbl.items())): snap.get("value", 0)
                  for lbl, snap in tm.ATTN_KERNEL_SELECTED.series()}
        on_tpu.select_kernel(4096, 4096, 10, 64)
        on_tpu.select_kernel(4096, 4096, 10, 64)   # dedup: one increment
        series = {tuple(sorted(lbl.items())): snap.get("value", 0)
                  for lbl, snap in tm.ATTN_KERNEL_SELECTED.series()}
        lbl = tuple(sorted({"tier": "packed", "geometry": key.key_str(),
                            "blocks": "256/512:k-streamed"}.items()))
        assert series.get(lbl, 0) - before.get(lbl, 0) == 1
        assert key.key_str() in on_tpu.selection_summary()

# (site, heads, head_dim, q_len, kv_len, dtype, prefer_flash, tp)
# → (tier, block_q, block_k), recorded from PR 27's tree (the parent of the
# PR that merged the two rule sets into one policy) with the platform
# reading ``tpu``: every site the benchmark's cells trace, a tp=2 shard of
# each, every geometry of the model zoo, the memory-constrained callers,
# and untabled geometries on both sides of each floor. A row changes only
# with the table row or the policy line that a PR means to change.
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
    ("policy.exact_length_below_floor", 16, 64, 1000, 1000, "bf16", False, 1,
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


# the shipped rows the policy does not answer the same, with the policy's
# tier: kept by hand. None today; the mechanism stays until the autotuner
# is timed on a chip and may bring one (ROADMAP D14).
HAND_KEPT_ROWS = {}


def test_dry_rebake_reproduces_the_shipped_table():
    """A dry bake writes what the one policy answers: every shipped row
    but the hand-kept ones comes back to the byte, and a hand-kept row
    differs from the policy (else it is not an exception: drop it)."""
    shipped = json.loads(autotune._SHIPPED_PATH.read_text())["entries"]
    assert set(HAND_KEPT_ROWS) <= set(shipped)
    for ks, row in shipped.items():
        baked = autotune.sweep_geometry(
            GeometryKey.from_key_str(ks), mode="dry").choice.to_dict()
        if ks in HAND_KEPT_ROWS:
            assert baked["tier"] == HAND_KEPT_ROWS[ks] != row["tier"]
        else:
            assert baked == row, ks


class TestGeometryDerivation:
    def test_zoo_geometries_cover_roofline_workloads(self):
        zoo = autotune.model_zoo_geometries()
        assert zoo["flux_joint"].num_heads * zoo["flux_joint"].head_dim \
            == 3072
        assert zoo["wan_self"].q_bucket >= 14040
        assert zoo["sdxl_self64"].q_bucket == 4096

    def test_geometries_for_txt2img_program(self):
        """UNet derivation straight from a tiny config — levels with
        transformer blocks contribute self+cross geometries at the
        level's downsampled token count."""
        from comfyui_distributed_tpu.cluster.shape_catalog import ProgramKey
        from comfyui_distributed_tpu.models.unet import UNetConfig

        cfg = UNetConfig(model_channels=64, channel_mult=(1, 2),
                         transformer_depth=(0, 1), head_dim=64,
                         context_dim=128)
        bundle = types.SimpleNamespace(
            pipeline=types.SimpleNamespace(
                unet=types.SimpleNamespace(config=cfg)),
            preset=types.SimpleNamespace(
                text=types.SimpleNamespace(max_len=77)))
        key = ProgramKey(pipeline="txt2img", model="tiny", height=256,
                         width=256, steps=4)
        geoms = autotune.geometries_for_program(bundle, key)
        # one transformer level: 256/8/2 = 16 → 256 tokens, 128ch → 2 heads
        assert GeometryKey.from_shape(2, 64, 256, 256) in geoms
        assert GeometryKey.from_shape(2, 64, 256, 77) in geoms


@pytest.mark.slow
class TestSweepCLI:
    """scripts/autotune_sweep.py end to end (the full zoo sweep — slow
    tier; the fast shipped-table assertions above ride tier-1)."""

    def test_dry_run_rebakes_identical_table(self, tmp_path):
        import json
        import subprocess
        import sys
        from pathlib import Path

        repo = Path(__file__).resolve().parents[1]
        out = tmp_path / "rebaked.json"
        proc = subprocess.run(
            [sys.executable, str(repo / "scripts" / "autotune_sweep.py"),
             "--dry-run", "--out", str(out)],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rebaked = json.loads(out.read_text())["entries"]
        shipped = json.loads(
            (repo / "comfyui_distributed_tpu" / "ops"
             / "attn_table_default.json").read_text())["entries"]
        # the policy reproduces the shipped bake but for the hand-kept
        # rows — drift means someone changed policy/legality without
        # re-baking
        assert set(rebaked) == set(shipped)
        for ks in set(shipped) - set(HAND_KEPT_ROWS):
            assert rebaked[ks] == shipped[ks], ks

    def test_explicit_geometry_sweep(self, tmp_path):
        import json
        import subprocess
        import sys
        from pathlib import Path

        repo = Path(__file__).resolve().parents[1]
        out = tmp_path / "one.json"
        proc = subprocess.run(
            [sys.executable, str(repo / "scripts" / "autotune_sweep.py"),
             "--dry-run", "--out", str(out),
             "--geometry", "h12.d128.q16384.kv16384.bf16"],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        entries = json.loads(out.read_text())["entries"]
        assert list(entries) == ["h12.d128.q16384.kv16384.bf16"]
        assert entries["h12.d128.q16384.kv16384.bf16"]["tier"] == "packed"


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
        with the kernels forced on, at the shortest ragged length past
        the packed floor: the site projects with ``nn.Dense`` and runs
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
