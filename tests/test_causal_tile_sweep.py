"""``scripts/causal_tile_sweep.py``'s arithmetic — the grid steps it counts
are the kernels' own rule, the pairs it counts are the masks' — and its
refusal to time a tile anywhere but on a TPU."""

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def sweep():
    spec = importlib.util.spec_from_file_location(
        "causal_tile_sweep", ROOT / "scripts" / "causal_tile_sweep.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module     # a dataclass looks its module up
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("position", [0, 1])
@pytest.mark.parametrize("block_q,block_k", [(1024, 1024), (2048, 512),
                                             (512, 2048), (4096, 1024)])
def test_a_counted_step_is_a_block_the_mask_reaches(sweep, position, block_q,
                                                    block_k):
    """The band's two geometries, by the mask itself: a visible step is a
    (q block, K block) tile that holds a pair some query sees."""
    g = sweep.geometries()["gqa_window"]
    start, lowest = sweep._bounds(g, position)
    row = (start + np.arange(g.chunk))[:, None]
    col = np.arange(g.rows)[None, :]
    seen = (col <= row) & (col >= lowest) & (col > row - g.window)
    tiles = seen.reshape(g.chunk // block_q, block_q,
                         g.rows // block_k, block_k).any(axis=(1, 3))
    visible, skipped = sweep.grid_steps(g, position, block_q, block_k, g.rows)
    assert visible == g.heads * int(tiles.sum())
    assert visible + skipped == g.heads * tiles.size


def test_the_issues_counts_at_the_old_tile(sweep):
    """ISSUE 40: at 1024 × 1024 Trinity's 32 chunks are 396 288 visible and
    396 288 skipped steps, Kimi's five layers skip 199 680 of 368 640."""
    gs = sweep.geometries()
    t = [sweep.grid_steps(gs["gqa_causal"], p, 1024, 1024, 132096)
         for p in range(32)]
    assert (sum(v for v, _ in t), sum(s for _, s in t)) == (396288, 396288)
    k = [sweep.grid_steps(gs["latent_causal"], p, 1024, 1024, 36864)
         for p in range(8)]
    assert gs["latent_causal"].calls * sum(s for _, s in k) == 199680
    assert gs["latent_causal"].calls * sum(v + s for v, s in k) == 368640


# (kernel, tile, rows) → (visible, skipped over the whole buffer, skipped
# over the traced extent) a request: ISSUE 63's counts and the band's
STEPS_63 = [
    ("gqa_causal_zaya", (2048, 2048), 131072, (166400, 161280, 2560)),
    ("gqa_causal", (2048, 2048), 133120, (99840, 99840, 1536)),
    ("shared_kv_causal", (2048, 2048), 67584, (21120, 21120, 640)),
    ("gqa_window", (1024, 1024), 8192, (120960, 75648, 1152)),
]


@pytest.mark.parametrize("name,tile,rows,want", STEPS_63)
def test_the_traced_extent_leaves_the_diagonals_few_skipped_steps(
        sweep, name, tile, rows, want):
    """ISSUE 63: ZAYA's ten layers walk 327 680 grid steps a request over
    the whole buffer, 166 400 of them visible; under the kernel's traced K
    extent a query tile's only skipped steps are the ones past its own last
    block — and under a band a tile walks from ITS first visible block."""
    g = sweep.geometries()[name]
    weights = sweep.positions_of(g, None)
    total = {}
    for extent in ("whole", "chunk"):
        steps = [sweep.grid_steps(g, p, *tile, rows, extent) for p in weights]
        total[extent] = tuple(
            g.calls * sum(w * s[k] for s, w in zip(steps, weights.values()))
            for k in (0, 1))
    assert total["whole"] == want[:2]
    assert total["chunk"] == (want[0], want[2])


def test_a_form_is_a_tile_a_part_of_its_rows_and_an_extent(sweep):
    """A part divides the query tile and is shorter; ``none`` is the plain
    step; the latent kernel has one form a tile."""
    forms = sweep.forms_of("gqa_causal", [(2048, 2048), (1024, 2048)],
                           [None, 2048, 1024, 128, 96], ["whole", "chunk"])
    assert [f[:3] for f in forms if f[3] == "chunk"] == [
        (2048, 2048, None), (2048, 2048, 1024), (2048, 2048, 128),
        (1024, 2048, None), (1024, 2048, 128)]
    assert len(forms) == 10
    assert sweep.forms_of("latent_causal", [(2048, 1024)], [None, 128],
                          ["whole", "chunk"]) == [(2048, 1024, None, "whole")]
    assert sweep.label(2048, 2048, 128, "chunk") == "2048x2048/128 chunk"
    assert sweep.label(1024, 1024, None, "whole") == "1024x1024 whole"


def test_the_counted_pairs_are_the_models_attended_keys(sweep):
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig

    gs = sweep.geometries()
    want = TrinityConfig.trinity_share().attended_keys(131072, 0)
    full, band = gs["gqa_causal"], gs["gqa_window"]
    assert full.calls * sum(sweep.counted_pairs(full, p)
                            for p in range(full.chunks)) \
        == want["full", "prefill"]
    weights = sweep.positions_of(band, None)
    assert weights == {0: 1, 1: 31}
    assert band.calls * sum(w * sweep.counted_pairs(band, p)
                            for p, w in weights.items()) \
        == want["window", "prefill"]


def test_sampled_positions_stand_for_every_chunk(sweep):
    g = sweep.geometries()["gqa_causal"]
    assert sweep.positions_of(g, None) == {p: 1 for p in range(32)}
    sampled = sweep.positions_of(g, "0,1,3,7,15,31")
    assert sum(sampled.values()) == 32 and set(sampled) == {0, 1, 3, 7, 15, 31}


def test_the_fit_recovers_a_visible_and_a_skipped_step(sweep):
    visible, skipped = np.array([10, 20, 30]), np.array([30, 20, 10])
    a, b = sweep.fit_steps(visible, skipped,
                           4.0e-6 * visible + 0.35e-6 * skipped)
    assert a == pytest.approx(4.0) and b == pytest.approx(0.35)
    assert sweep.fit_steps([5, 5], [3, 3], [1e-3, 1e-3]) == (None, None)


def test_the_geometries_are_the_presets_and_name_their_shipped_pair(sweep):
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig

    cfg = TrinityConfig.trinity_share()
    gs = sweep.geometries()
    assert gs["gqa_causal"].shipped == (cfg.attn_full_block_q,
                                        cfg.attn_full_block_k)
    assert gs["gqa_window"].shipped == (cfg.attn_window_block_q,
                                        cfg.attn_window_block_k)
    assert gs["gqa_causal"].shipped != gs["gqa_window"].shipped
    for g in gs.values():
        assert g.shipped in g.candidates and g.chunk == 4096
    zaya = gs["gqa_causal_zaya"]
    assert (zaya.heads, zaya.kv_heads, zaya.calls, zaya.chunks) \
        == (8, 2, 10, 32)
    assert (gs["gqa_causal"].kv_heads, gs["shared_kv_causal"].kv_heads) \
        == (8, 1)


def test_a_tile_is_never_timed_off_the_chip(sweep):
    with pytest.raises(SystemExit, match="timed on a TPU"):
        sweep.sweep("gqa_window", None, [None], ["chunk"], None, 1, None)
