"""The eighth prompt rewriter (latent attention over the keys a learned
indexer picks for every query, an index-key cache beside the latent cache,
routed experts by group) at the tiny float32 preset, against the plain
reference on seeded weights — logits, not tokens: the chunked prefill and
decode through both caches with ``index_topk`` BELOW the prompt length, the
selection against ``lax.top_k`` (ties, short prefixes), the three kernels in
the interpreter against their ``jnp`` forms, the expert share, the shared
pipeline, the nodes, the shipped graph, and the benchmark's files, counts
and readers of the cell."""

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_glm as G
from comfyui_distributed_tpu.models import llm_glm_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import expert_share
from comfyui_distributed_tpu.ops import index_select_attention as ops

ROOT = Path(__file__).resolve().parent.parent
# a float32 program against the float32 reference: logits of unit scale
# through 5 layers: 4e-6 measured; 2e-4 leaves fifty times that and is two
# orders under what one wrong key, a dropped ReLU or a missing rope reads
F32_TOL = 2e-4
CFG = G.GlmConfig.tiny()
CELL = "glm-5.brief64k-sdxl8"
T, NEW = 40, 6


@pytest.fixture(scope="module")
def params():
    return G.init_glm(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (T + NEW,), 0,
                              CFG.vocab_size)


@pytest.fixture(scope="module")
def full_logits(params, ids):
    return R.forward(CFG, params, ids)[0]


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# --- the model against the reference ------------------------------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.index_topk < T                      # the selection bites
    assert CFG.v_head_dim != CFG.qk_nope_head_dim  # a value of its own width
    assert CFG.moe_layers == [1, 2, 3, 4] and not CFG.is_moe(0)
    assert T > 2 * CFG.prefill_chunk_tokens and T % CFG.prefill_chunk_tokens
    assert CFG.routing == expert_share.Routing(16, 4, 1, 1, 2.5)
    full = G.GlmConfig.glm_share()
    assert full.softmax_scale == 1 / 16
    assert full.index_weight_scale == pytest.approx(32 ** -0.5 * 128 ** -0.5)
    assert full.routing == expert_share.Routing(256, 8, 1, 1, 2.5)


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("chunk", [16, 8, 10, T])
def test_chunked_prefill_is_the_reference_at_every_position(
        params, ids, full_logits, kernel, chunk):
    """Across chunk boundaries, with a padded last chunk (16, 10) and
    whole; every position reads only its 12 keys."""
    logits, cache, held = G.prefill(CFG, params, ids[:T], T + NEW,
                                    all_logits=True, chunk=chunk,
                                    kernel=kernel)
    assert close(logits, full_logits[:T])
    assert cache["ki"][0].shape == (max(T + NEW, -(-T // chunk) * chunk),
                                    CFG.index_head_dim)


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        params, ids, full_logits):
    logits, cache, _ = G.prefill(CFG, params, ids[:T], T + NEW)
    assert close(logits, full_logits[T - 1])
    step = jax.jit(lambda c, t, p: G.decode_step(CFG, params, c, t, p))
    formed = G.decode_weights(CFG, params)
    for j in range(T, T + NEW):
        other = G.decode_step(CFG, formed, cache, ids[j], j)[0]
        logits, cache, held = step(cache, ids[j], j)
        assert close(logits, full_logits[j]), j
        assert close(other, logits, 1e-5)      # the form made ahead
        assert held.shape == (4,)


def test_with_index_topk_past_the_prompt_it_is_dense_latent_attention(
        params, ids):
    """``index_topk ≥ T``: every query keeps its whole prefix, and the
    model is the reference GIVEN the causal mask — plain MLA."""
    dense = dataclasses.replace(CFG, index_topk=64)
    want = R.forward(dense, params, ids[:T], given=lambda i, lo, n: (
        lo + jnp.arange(n)[:, None] >= jnp.arange(T)[None, :]))[0]
    got = G.prefill(dense, params, ids[:T], T, all_logits=True)[0]
    assert close(got, want)
    assert close(R.forward(dense, params, ids[:T])[0], want)
    assert not close(G.prefill(CFG, params, ids[:T], T,
                               all_logits=True)[0], want)


def test_the_reference_in_query_blocks_and_given_a_selection_is_itself(
        params, ids, full_logits):
    blocked, held = R.forward(CFG, params, ids, block=16)
    assert close(blocked, full_logits, 1e-5)
    taps = {}
    R.forward(CFG, params, ids, block=16,
              tap=lambda i, lo, s: taps.setdefault(i, []).append(s))
    own = [R.select(jnp.concatenate(taps[i]), CFG.index_topk)
           for i in range(CFG.num_hidden_layers)]
    assert all(int(m[t].sum()) == min(CFG.index_topk, t + 1)
               for m in own for t in (0, 5, 11, 12, 45))
    given = R.forward(CFG, params, ids, block=16, given=lambda i, lo, n:
                      own[i][lo:lo + n])[0]
    assert close(given, full_logits, 1e-5)
    # someone else's selection is another answer
    shifted = R.forward(CFG, params, ids, given=lambda i, lo, n: jnp.roll(
        own[i][lo:lo + n], 1, axis=1) | jnp.eye(T + NEW, dtype=bool)[
            lo:lo + n])[0]
    assert not close(shifted, full_logits)


def test_a_bfloat16_run_fails_the_float32_tolerance(params, ids, full_logits):
    low = dataclasses.replace(CFG, dtype="bfloat16")
    got = G.prefill(low, params, ids[:T], T + NEW, all_logits=True)[0]
    assert not close(got, full_logits[:T])


# --- the selection -------------------------------------------------------------


# where a chunk of 16 queries starts in a cache of 512 columns searched in
# tiles of 128: a step's rows see one tile, three (its rows 250 … 257 cross
# into the third) or all four
_CHUNKS = {"first chunk": 0, "middle chunk": 250, "last chunk": 496}


def _scores(case: str, n: int = 16, S: int = 48, first: int = 20):
    """``(scores, the first row's position, the kernel's column tile)``: 48
    columns are searched whole (no tile divides them)."""
    tile = S
    if case in _CHUNKS:
        S, first, tile = 512, _CHUNKS[case], 128
    s = jax.random.normal(jax.random.key(3), (n, S), jnp.float32)
    if case == "ties":
        s = jnp.round(s * 2) / 2              # a dozen values: many ties
    elif case == "all equal":
        s = jnp.zeros((n, S), jnp.float32)
    elif case == "negative":
        s = -jnp.abs(s) - 1.0
    elif case == "short prefix":
        first = 0                       # rows 0 … 15 see 1 … 16 keys
    return s, first, tile


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("topk", [1, 7, 12, 64])
@pytest.mark.parametrize("case", ["random", "ties", "all equal", "negative",
                                  "short prefix", *_CHUNKS])
def test_the_selection_is_lax_top_ks_position_for_position(case, topk,
                                                          kernel):
    """Exactly ``min(topk, t + 1)`` keys a row, ties to the lower position,
    the whole prefix where it is short — ``lax.top_k``'s set, and nothing
    in ANY column past a row's position: the kernel's 8-row steps search
    the column tiles their rows see (at the first chunk ``t + 1 = topk``
    falls inside a step for ``topk`` 7 and 12) and write the rest."""
    scores, first, tile = _scores(case)
    n, S = scores.shape
    if kernel == "lax":
        keep = ops.select_keep_lax(scores, first, topk)
    else:
        keep = ops.index_select_keep(scores, first, topk=topk, rows=8,
                                     tile=tile, interpret=True)
        assert np.array_equal(np.asarray(keep), np.asarray(
            ops.select_keep_lax(scores, first, topk)))
    seen = first + jnp.arange(n)[:, None] >= jnp.arange(S)[None, :]
    want = R.select(jnp.where(seen, scores, -jnp.inf), topk)
    assert keep.shape == (n, S) and not np.asarray(keep)[~np.asarray(seen)].any()
    assert np.array_equal(np.asarray(keep) != 0, np.asarray(want))
    assert np.array_equal(np.asarray(keep).sum(1),
                          np.minimum(topk, first + np.arange(n) + 1))


@pytest.mark.parametrize("case", ["in one tile", "across a tile boundary",
                                  "no tile divides the columns"])
def test_a_forced_tie_goes_to_the_lower_position(case):
    scores = jnp.asarray([[0.5, 2.0, 1.0, 1.0, 1.0, 0.1, 1.0, 3.0]])
    if case == "in one tile":
        for kernel in ("lax", "interpret"):
            keep = ops.select_keep(scores, 7, 4, kernel)
            assert np.asarray(keep)[0].tolist() == [0, 1, 1, 1, 0, 0, 0, 1]
        rows, valid = ops.index_step(
            jnp.ones((1, 1)), jnp.ones((1,)), scores[0][:, None], 7, 4,
            jnp.float32)
        assert sorted(np.asarray(rows).tolist()) == [1, 2, 3, 7] \
            and valid.all()
        return
    if case == "across a tile boundary":
        # eight rows at 300 …: six keys tie at 1.0 around column 128, the
        # end of the first tile of three the step sees; two lie above them
        S, tile, first, tied = 512, 128, 300, [125, 127, 128, 129, 140, 290]
        scores = jnp.zeros((8, S)).at[:, jnp.asarray(tied)].set(1.0) \
            .at[:, jnp.asarray([5, 260])].set(2.0)
        for topk, kept in ((4, [5, 125, 127, 260]),
                           (5, [5, 125, 127, 128, 260]),
                           (8, sorted([5, 260, *tied]))):
            keep = np.asarray(ops.index_select_keep(
                scores, first, topk=topk, rows=8, tile=tile, interpret=True))
            assert all(np.flatnonzero(row).tolist() == kept for row in keep)
            assert np.array_equal(keep, np.asarray(
                ops.select_keep_lax(scores, first, topk)))
        return
    # the served tile where it divides the cache, else the whole width —
    # through the caller's rule, as the model reaches the kernel
    assert ops.select_tile(17 * 4096) == ops.SELECT_TILE == 4096
    assert ops.select_tile(17 * 4096 + 128) == 17 * 4096 + 128
    scores = jnp.tile(scores, (ops.SELECT_ROWS, 6))              # [32, 48]
    assert ops.select_tile(scores.shape[1]) == 48
    keep = ops.select_keep(scores, 9, 4, "interpret")
    assert np.array_equal(np.asarray(keep),
                          np.asarray(ops.select_keep_lax(scores, 9, 4)))
    assert np.asarray(keep)[0].tolist() == [0, 1, 1, 0, 0, 0, 0, 1, 0, 1] \
        + [0] * 38


@pytest.mark.parametrize("model", ["glm-5", "keye-vl-2.0-30b-a3b"])
def test_over_a_64k_brief_the_selection_searches_half_the_caches_columns(
        model):
    """``cdt_llm_select_columns_total``'s arithmetic, by the kernel's own
    rule: 16 chunks of 4096 over a cache of 17, a 64-row step of chunk
    ``i`` visits ``i + 1`` tiles of 4096 — 557 056 of 1 114 112 columns a
    row's walk — and a cache one tile holds is searched whole."""
    from comfyui_distributed_tpu.models.llm_keye import KeyeConfig

    cfg = G.GlmConfig.glm_share() if model == "glm-5" \
        else KeyeConfig.keye_share()
    assert (cfg.prefill_chunk_tokens, cfg.select_rows) == (4096, 1024)
    steps = 4096 // ops.SELECT_ROWS * cfg.num_hidden_layers
    assert cfg.select_columns(65536, 128) == {
        "searched": 557_056 * steps, "cache": 1_114_112 * steps}
    one = ops.select_columns(65536, 128, 4096, 1024)
    # a padded last chunk selects too, over a cache of the 16 chunks walked
    assert ops.select_columns(65536 - 4000, 128, 4096, 1024) == {
        "searched": one["searched"], "cache": 16 * 4096 * 1024}
    assert one["searched"] / one["cache"] == 0.5
    # the visible tiles of every step, one by one, as the kernel counts them
    tile, S = ops.select_tile(17 * 4096), 17 * 4096
    assert one["searched"] == sum(
        -(-(first + ops.SELECT_ROWS) // tile) * tile
        for first in range(0, 65536, ops.SELECT_ROWS))
    # 4096 + 128 tokens: one chunk over a cache of two
    assert ops.select_columns(4096, 128, 4096, 1024) == {
        "searched": 4096 * 64, "cache": 2 * 4096 * 64}
    tiny = type(cfg).tiny()
    assert len(set(tiny.select_columns(40, 8).values())) == 1


def _sweep():
    spec = importlib.util.spec_from_file_location(
        "index_select_sweep", Path(__file__).resolve().parent.parent
        / "scripts" / "index_select_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    return sweep


@pytest.mark.parametrize("form", ["copied tiles", "whole rows"])
@pytest.mark.parametrize("case", list(_CHUNKS))
def test_the_sweeps_forms_that_did_not_ship_select_the_same_keys(case, form):
    """``scripts/index_select_sweep.py`` times the shipped kernel against
    the scores left in HBM (a step copies in the tiles its rows see) and
    against PR 51's whole rows: both are the plain form's mask bit for bit,
    or the comparison is of different work."""
    sweep = _sweep()
    scores, first, tile = _scores(case)
    for topk in (7, 64):
        if form == "copied tiles":
            keep = sweep.select_copied_tiles(scores, first, topk, 8, tile,
                                             interpret=True)
        else:
            keep = sweep.select_whole_rows(scores, first, topk, 8,
                                           interpret=True)
        assert np.array_equal(np.asarray(keep), np.asarray(
            ops.select_keep_lax(scores, first, topk)))


def test_order_key_orders_as_the_floats_do():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf])
    key = np.asarray(ops.order_key(x))
    assert (np.diff(key) > 0).all()
    assert int(ops.order_key(jnp.float32(-0.0) + 0.0)) == 0


def test_decode_keeps_the_rows_the_reference_selects(params, ids):
    _, cache, _ = G.prefill(CFG, params, ids[:T], T + NEW)
    taps = {}
    R.forward(CFG, params, ids[:T + 1],
              tap=lambda i, lo, s: taps.setdefault(i, s))
    *_, kept = G.decode_step(CFG, params, cache, ids[T], T, keep_rows=True)
    for i, (rows, valid) in enumerate(kept):
        want = np.flatnonzero(np.asarray(R.select(taps[i], CFG.index_topk))[T])
        assert bool(valid.all())
        assert sorted(np.asarray(rows).tolist()) == want.tolist(), i
    # a position below index_topk reads its whole prefix, the rest masked
    rows, valid = ops.index_step(
        jnp.ones((2, 4)), jnp.ones((2,)), jnp.ones((32, 4)), 4, 12,
        jnp.float32)
    assert sorted(np.asarray(rows)[np.asarray(valid)].tolist()) \
        == [0, 1, 2, 3, 4]


# --- each kernel in the interpreter against its jnp form -----------------------


@pytest.mark.parametrize("start", [0, 16, 48])
def test_the_score_kernel_is_the_plain_sum_over_the_index_heads(start):
    kq, kw, kk = jax.random.split(jax.random.key(4), 3)
    C, J, d, S = 16, 4, 16, 64
    q_i = jax.random.normal(kq, (C, J, d))
    w = jax.random.normal(kw, (C, J))
    k_i = jax.random.normal(kk, (S, d))
    want = ops.index_scores_lax(q_i, w, k_i, jnp.float32)
    got = ops.index_score_sums(jnp.swapaxes(q_i, 0, 1), w, k_i, start,
                               block_q=8, block_k=16, interpret=True)
    seen = start + np.arange(C)[:, None] >= np.arange(S)[None, :]
    assert np.allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                       atol=1e-5)
    assert np.asarray(want).min() < 0 < np.asarray(want).max()
    # the ReLU sits inside the sum over heads, the weight outside it
    one = float(sum(w[3, j] * max(float(q_i[3, j] @ k_i[5]), 0.0)
                    for j in range(J)))
    assert float(want[3, 5]) == pytest.approx(one, abs=1e-5)


@pytest.mark.parametrize("start", [0, 32])
def test_the_masked_kernel_is_a_softmax_over_the_kept_keys(start):
    keys = jax.random.split(jax.random.key(5), 4)
    C, H, dk, dv, S = 16, 3, 16, 24, 64
    q = jax.random.normal(keys[0], (C, H, dk)) / 4
    k = jax.random.normal(keys[1], (S, H, dk))
    v = jax.random.normal(keys[2], (S, H, dv))
    seen = start + np.arange(C)[:, None] >= np.arange(S)[None, :]
    keep = (jax.random.uniform(keys[3], (C, S)) < 0.3) & seen
    keep = keep.at[:, 0].set(True).astype(jnp.int8)
    want = ops.masked_attention_lax(q, k, v, keep, jnp.float32)
    got = ops.index_masked_mha(
        q.reshape(C, -1), k.reshape(S, -1), v.reshape(S, -1), keep, start,
        num_heads=H, block_q=8, block_k=16, part=16, interpret=True)
    assert np.allclose(np.asarray(got).reshape(C, H, dv), np.asarray(want),
                       atol=1e-5)


_CORE_CHUNKS = {"first chunk": 0, "middle chunk": 32, "padded last chunk": 96}


def _core_case(start: int, C: int = 32, H: int = 2, dk: int = 16,
               dv: int = 24, S: int = 128, topk: int = 24):
    """A chunk of 32 queries at ``start`` over a cache of four chunks, its
    mask the selection's own (the first chunk's rows have fewer keys than
    ``topk``: they keep every one): ``(q, k, v, keep, want [C,H,dv])``."""
    keys = jax.random.split(jax.random.key(9), 4)
    q = jax.random.normal(keys[0], (C, H, dk)) / 4
    k = jax.random.normal(keys[1], (S, H, dk))
    v = jax.random.normal(keys[2], (S, H, dv))
    keep = ops.select_keep_lax(jax.random.normal(keys[3], (C, S)), start,
                               topk)
    kept = np.asarray(keep).sum(1)
    assert (kept == np.minimum(start + np.arange(C) + 1, topk)).all()
    want = ops.masked_attention_lax(q, k, v, keep, jnp.float32)
    return (q.reshape(C, -1), k.reshape(S, -1), v.reshape(S, -1), keep,
            np.asarray(want))


@pytest.mark.parametrize("tile", [(16, 16, 8), (32, 32, 8), (32, 16, 16)],
                         ids=["below the chunk", "the chunk",
                              "a whole K tile a product"])
@pytest.mark.parametrize("chunk", list(_CORE_CHUNKS))
def test_the_masked_kernel_by_parts_of_a_k_tile_under_a_grid_that_follows_the_chunk(
        chunk, tile):
    """``index_masked_mha`` at a query tile below the chunk and at the
    chunk, a K tile's logits in products of ``part`` keys (PR 60), for the
    first chunk, a middle one and the last of a cache of whole chunks: the
    plain softmax over the kept keys — and the rows past the chunk's end,
    which the fill never writes, are never read: they hold NaN."""
    start = _CORE_CHUNKS[chunk]
    q, k, v, keep, want = _core_case(start)
    C, (bq, bk, part) = q.shape[0], tile
    got = ops.index_masked_mha(
        q, k.at[start + C:].set(jnp.nan), v.at[start + C:].set(jnp.nan),
        keep, start, num_heads=2, block_q=bq, block_k=bk, part=part,
        interpret=True)
    assert np.allclose(np.asarray(got).reshape(want.shape), want, atol=1e-5)


@pytest.mark.parametrize("C,S,tile", [
    (4096, 69632, ops.CORE_TILE), (4096, 69632, (1024, 1024)),
    (4096, 69632, (4096, 512)), (32, 128, (16, 16)), (32, 160, (8, 32)),
    (16, 48, (16, 16))])
def test_the_cores_grid_ends_where_the_chunks_last_row_sees(C, S, tile):
    """``core_k_steps``: every chunk's K extent covers each of its query
    tiles' last visible block, ends AT the last tile's, and the last chunk
    of a cache of whole chunks takes the whole grid."""
    from comfyui_distributed_tpu.ops.flash_latent import _last_block

    bq, bk = tile
    nk = S // bk
    for start in range(0, S, C):
        steps = int(ops.core_k_steps(start, C, bk, nk))
        last = [int(_last_block(start, i, bq, bk, nk))
                for i in range(C // bq)]
        assert max(last) == steps - 1 and steps <= nk, start
    assert int(ops.core_k_steps(S - C, C, bk, nk)) == nk
    assert int(ops.core_k_steps(0, C, bk, nk)) == -(-C // bk)


@pytest.mark.parametrize("tile,extent,visible,skipped", [
    ((1024, 1024), "whole", 665_600, 727_040),
    ((2048, 1024), "whole", 337_920, 358_400),
    ((4096, 1024), "whole", 174_080, 174_080),
    ((1024, 1024), "quarters", 665_600, 204_800),
    ((2048, 1024), "quarters", 337_920, 97_280),
    ((1024, 1024), "chunk", 665_600, 30_720),
    ((2048, 1024), "chunk", 337_920, 10_240),
    ((4096, 1024), "chunk", 174_080, 0),
    ((2048, 2048), "whole", 168_960, 179_200),
    ((2048, 2048), "chunk", 168_960, 5_120)])
def test_the_sweep_counts_the_cores_grid_steps_as_issue_60_did(
        tile, extent, visible, skipped):
    """``scripts/index_select_sweep.core_grid_steps`` at the cell's geometry
    (16 chunks of 4096 against 69 632 rows), times 5 layers × 64 heads: the
    steps that multiply a K block and the ones that do nothing, by tile and
    by how far the grid's K axis goes."""
    sweep = _sweep()
    assert (sweep.C, sweep.S, sweep.CHUNKS, sweep.H) == (4096, 69632, 16, 64)
    got = sweep.core_grid_steps(sweep.C, sweep.S, sweep.CHUNKS, *tile, extent)
    assert tuple(5 * sweep.H * n for n in got) == (visible, skipped)
    assert sweep.quarter_lengths(68) == [17, 34, 51, 68]
    assert sweep.core_tile("2048x2048/512") == (2048, 2048, 512)
    assert sweep.core_tile("2048x1024") == (2048, 1024, 1024)


@pytest.mark.parametrize("extent", ["whole", "quarters", "chunk"])
@pytest.mark.parametrize("chunk", list(_CORE_CHUNKS))
def test_the_sweeps_k_extents_attend_alike(chunk, extent):
    """The sweep times the shipped grid (``chunk``) against the whole padded
    cache at every chunk and against four static lengths picked by
    ``lax.switch``: the same attention, or the comparison is of different
    work."""
    start = _CORE_CHUNKS[chunk]
    q, k, v, keep, want = _core_case(start)
    got = _sweep().core_form(extent, 2, 16, 16, 8, interpret=True)(
        q, k, v, keep, jnp.int32(start))
    assert np.allclose(np.asarray(got).reshape(want.shape), want, atol=1e-5)


@pytest.mark.parametrize("heads_per_pass", [2, 3])
@pytest.mark.parametrize("start", [0, 16, 32])
def test_the_fill_kernel_is_the_plain_decompression_of_the_rows_a_chunk_sees(
        start, heads_per_pass):
    """``index_fill_kv`` at the tiny preset's geometry, for the first, a
    middle and the last chunk of a cache of three, by groups of two heads
    and (3 does not divide 4) of one: a key ``[c W_k | k_rope]`` and a value
    ``c W_v``, a head's columns together, on every row below the chunk's
    end."""
    keys = jax.random.split(jax.random.key(8), 3)
    C, H, nope, rope, v, rank = (
        CFG.prefill_chunk_tokens, CFG.num_attention_heads,
        CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.v_head_dim,
        CFG.kv_lora_rank)
    S, n = 3 * C, start + C
    c = jax.random.normal(keys[0], (S, rank))
    kr = jax.random.normal(keys[1], (S, rope))
    w_b = jax.random.normal(keys[2], (rank, H * (nope + v))) / 4
    kv = jnp.dot(c, w_b, precision="highest").reshape(S, H, nope + v)
    want_k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr[:, None], (S, H, rope))], -1)
    g = math.gcd(H, heads_per_pass)
    assert g == (2 if heads_per_pass == 2 else 1)
    kr_wide, w_k, w_v = ops.fill_operands(kr, w_b, H, nope, g, jnp.float32)
    assert w_k.shape == (H // g, rank, g * (nope + rope))
    assert w_v.shape == (H // g, rank, g * v)
    for i in range(H // g):
        k_ws, v_ws = ops.index_fill_kv(
            c, kr_wide, w_k[i], w_v[i], n, num_heads=g, nope=nope,
            block_rows=8, interpret=True)
        assert k_ws.shape == (S, g * (nope + rope))
        assert v_ws.shape == (S, g * v)
        heads = slice(i * g, (i + 1) * g)
        assert np.allclose(np.asarray(k_ws[:n]).reshape(n, g, -1),
                           np.asarray(want_k[:n, heads]), atol=1e-5)
        assert np.allclose(np.asarray(v_ws[:n]).reshape(n, g, -1),
                           np.asarray(kv[:n, heads, nope:]), atol=1e-5)
        # the rope key passes through untouched
        assert np.array_equal(
            np.asarray(k_ws[:n]).reshape(n, g, -1)[..., nope:],
            np.asarray(want_k[:n, heads, nope:]))


@pytest.mark.parametrize("heads_per_pass", [1, 2, 4, 3])
def test_the_chunks_attention_by_groups_of_heads_is_the_whole(heads_per_pass,
                                                              monkeypatch):
    """The workspace holds ``heads_per_pass`` heads' keys and values of the
    rows the chunk sees; rows above it are never read — neither the caches'
    by the fill nor the workspace's, which the fill leaves unwritten, by the
    attention: both may hold NaN."""
    keys = jax.random.split(jax.random.key(6), 6)
    C, H, nope, rope, v, rank, S, start = 16, 4, 8, 8, 16, 16, 48, 16
    q_nope = jax.random.normal(keys[0], (C, H, nope))
    q_rope = jax.random.normal(keys[1], (C, H, rope))
    c = jax.random.normal(keys[2], (S, rank))
    kr = jax.random.normal(keys[3], (S, rope))
    w_b = jax.random.normal(keys[4], (rank, H * (nope + v))) / 4
    seen = start + np.arange(C)[:, None] >= np.arange(S)[None, :]
    keep = ((jax.random.uniform(keys[5], (C, S)) < 0.4) & seen
            ).at[:, 0].set(True).astype(jnp.int8)
    want = ops.masked_chunk_attention(q_nope, q_rope, c, kr, keep, start,
                                      w_b, 0.25, jnp.float32, "lax")
    fill, groups = ops.index_fill_kv, []

    def poisoning_fill(c, kr_wide, w_k, w_v, n_rows, **kw):
        groups.append(kw["num_heads"])
        past = (jnp.arange(c.shape[0]) >= n_rows)[:, None]
        return tuple(jnp.where(past, jnp.nan, ws)
                     for ws in fill(c, kr_wide, w_k, w_v, n_rows, **kw))

    monkeypatch.setattr(ops, "index_fill_kv", poisoning_fill)
    got = ops.masked_chunk_attention(
        q_nope, q_rope, c.at[start + C:].set(jnp.nan),
        kr.at[start + C:].set(jnp.nan), keep, start, w_b, 0.25, jnp.float32,
        "interpret", heads_per_pass)
    assert groups == [math.gcd(H, heads_per_pass)]     # traced once, mapped
    assert got.shape == (C, H, v)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_absorbed_step_over_given_rows_is_attention_over_those_rows():
    keys = jax.random.split(jax.random.key(7), 5)
    H, nope, rope, v, rank, S = 4, 8, 8, 16, 16, 32
    q_nope = jax.random.normal(keys[0], (1, H, nope))
    q_rope = jax.random.normal(keys[1], (1, H, rope))
    c = jax.random.normal(keys[2], (S, rank))
    kr = jax.random.normal(keys[3], (S, rope))
    w_b = jax.random.normal(keys[4], (rank, H * (nope + v))) / 4
    rows = jnp.asarray([3, 30, 11, 0, 17, 5])
    valid = jnp.asarray([True, True, True, True, False, True])
    keep = jnp.zeros((1, S), jnp.int8).at[0, rows[valid]].set(1)
    want = ops.masked_chunk_attention(q_nope, q_rope, c, kr, keep, S - 1,
                                      w_b, 0.25, jnp.float32, "lax")[0]
    from comfyui_distributed_tpu.ops.latent_attention import absorbed_form

    for form in (w_b, absorbed_form(w_b, H)):
        got = ops.absorbed_rows_step(q_nope[0], q_rope[0], c[rows], kr[rows],
                                     valid, form, 0.25, jnp.float32)
        assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# --- the expert share ------------------------------------------------------------


def test_the_parts_of_all_four_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: every share routes over all 16 (with the
    selection bias) and computes its own four, in each of the three forms;
    the shared expert is added once."""
    uncut = dataclasses.replace(CFG, n_routed_experts=16, first_expert=0)
    m = G.init_glm(uncut, jax.random.key(8))["layers"][2]["moe"]
    m = {**m, "router_bias": m["router_bias"] * 20}     # it moves choices
    x = jax.random.normal(jax.random.key(10), (9, CFG.hidden_size))
    want, want_held = R.experts(uncut, m, x)
    idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                uncut.routing)
    bare, _ = expert_share.route(x, m["w_router"], None, uncut.routing)
    assert not np.array_equal(np.sort(idx, -1), np.sort(bare, -1))
    total = expert_share.swiglu(x, m["shared"]["w_gu"], m["shared"]["w_down"],
                                jnp.float32)
    held = 0
    for first in range(0, 16, 4):
        share = {k: m[k][first:first + 4] for k in ("e_gu", "e_down")}
        dense = expert_share.held_part_dense(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32)
        grouped, _ = expert_share.held_part_grouped(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32,
            tile=2)
        token = jnp.stack([expert_share.held_part_token(
            x[t], idx[t], w[t], share["e_gu"], share["e_down"], first,
            jnp.float32) for t in range(9)])
        assert close(dense, token, 1e-5) and close(dense, grouped, 1e-5)
        total = total + grouped
        held += int(expert_share.held_slots(idx, first, 4).sum())
    assert close(total, want)
    assert held == int(want_held) == 9 * CFG.num_experts_per_tok


def test_a_share_leaves_out_what_absent_experts_would_add(params, ids):
    other = dataclasses.replace(CFG, first_expert=4)
    a = G.prefill(CFG, params, ids[:T], T)[0]
    b = G.prefill(other, params, ids[:T], T)[0]
    assert not close(a, b)
    assert close(b, R.forward(other, params, ids[:T])[0][-1])


def test_the_published_share_counts_what_the_issue_counted():
    cfg = G.GlmConfig.glm_share()
    assert G.param_count(cfg) == 2_701_673_216          # 2.702 G
    tree = G.init_glm(cfg, None, abstract=True)
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
    assert 5.02 < held / 2**30 < 5.04                    # 5.03 GiB
    layer = tree["layers"][1]
    attention = sum(math.prod(a.shape) for name, a in layer["attn"].items()
                    if name.startswith("w_"))
    assert attention == 165_019_648
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
        layer["indexer"])) == 9_371_904
    assert layer["moe"]["e_gu"].shape == (8, 6144, 4096)
    # 1408 B a token a layer: 65 664 positions x 5 layers in bfloat16
    sizes = llm_model.cache_bytes(cfg.model, cfg, 65536 + 128)
    assert sizes == {"latent": 5 * 65664 * 576 * 2,
                     "index": 5 * 65664 * 128 * 2}
    assert sum(sizes.values()) == 1408 * 5 * 65664
    pairs = cfg.attended_keys(65536, 128)
    assert pairs[("sparse", "prefill")] == 5 * 132_121_600   # 132.1 M a layer
    assert pairs[("sparse", "decode")] == 5 * 128 * 2048
    brute = sum(min(CFG.index_topk, t + 1) for t in range(T + NEW))
    tiny = CFG.attended_keys(T, NEW)
    assert sum(tiny.values()) == CFG.num_hidden_layers * brute


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_scans_the_continuation_inside_one_labelled_program(
        params, ids, full_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is G.MODEL
    assert pipe.prefill_plan(T) == (16, 3, "grouped")
    prefill, decode = pipe.programs(T, 8)
    logits, cache, held, rows = prefill(ids[:T])
    assert close(logits, full_logits[T - 1])
    assert held.shape == rows.shape == (4,)
    out, taps, slots, finite = decode(logits, cache, jax.random.key(3),
                                      jnp.asarray(0.7, jnp.float32))
    assert out.shape == (8,) and bool(finite) and slots.shape == (4,)
    full = pipeline_llm.LLMPipeline(G.GlmConfig.glm_share(), None)
    assert full.prefill_plan(65536) == (4096, 16, "grouped")


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["glm-tiny"].kind == PRESETS["glm-5"].kind == "llm"
    assert PRESETS["glm-5"].llm == G.GlmConfig.glm_share()
    assert PRESETS["glm-5"].llm.model is G.MODEL
    assert PRESETS["glm-tiny"].llm == CFG
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("glm-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("glm-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("glm-tiny") is bundle


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "glm-tiny"
    graph["9"]["inputs"].update(prompt_tokens=40, new_tokens=8)
    graph["4"]["inputs"].update(width=32, height=32, steps=1)
    graph["3"]["inputs"]["seed"] = seed
    graph["6"]["inputs"]["output_dir"] = str(tmp_path)
    return graph


def test_the_shipped_graph_runs_and_the_counters_move_as_stated(tmp_path):
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.executor import (GraphExecutor,
                                                        validate_prompt)
    from comfyui_distributed_tpu.telemetry import metrics as tm

    assert not validate_prompt(_shipped_graph(tmp_path, 1))
    executor = GraphExecutor()

    def read():
        return {
            "slots": {p: sum(tm.LLM_EXPERT_SLOTS.labels(where=k,
                                                        phase=p).value
                             for k in ("held", "absent"))
                      for p in ("prefill", "decode")},
            "keys": {p: tm.LLM_ATTN_KEYS.labels(layers="sparse",
                                                phase=p).value
                     for p in ("prefill", "decode")},
            "columns": {k: tm.LLM_SELECT_COLUMNS.labels(kind=k).value
                        for k in ("searched", "cache")},
            "chunks": tm.LLM_PREFILL_CHUNKS.labels().value}

    before = read()
    texts = [executor.execute(_shipped_graph(tmp_path, seed))["9"][0]
             for seed in (11, 11, 12)]
    assert texts[0] == texts[1] != texts[2]
    assert len(texts[0].split()) == 8
    assert all(w[0] == "t" and 0 <= int(w[1:]) < CFG.vocab_size
               for w in texts[0].split())
    if telemetry.enabled():
        after = read()
        for phase, tokens in (("prefill", 40), ("decode", 8)):
            assert after["slots"][phase] - before["slots"][phase] \
                == 3 * tokens * CFG.num_experts_per_tok * 4
        assert after["chunks"] - before["chunks"] == 3 * 3
        want = CFG.attended_keys(40, 8)
        for phase in ("prefill", "decode"):
            assert after["keys"][phase] - before["keys"][phase] \
                == 3 * want[("sparse", phase)]
        # three chunks of 16 in calls of 8 rows over 48 columns, 5 layers:
        # the tiny preset's one tile is its whole cache
        for kind in ("searched", "cache"):
            assert after["columns"][kind] - before["columns"][kind] \
                == 3 * 5 * 6 * 48 == 3 * CFG.select_columns(40, 8)[kind]
        assert tm.LLM_CACHE_POSITIONS.labels().value == 48
        assert tm.LLM_CACHE_BYTES.labels(layers="latent").value \
            == 5 * 48 * 24 * 4
        assert tm.LLM_CACHE_BYTES.labels(layers="index").value \
            == 5 * 48 * 16 * 4


def test_the_three_pieces_are_told_apart_below_the_attention_scope(params,
                                                                   ids):
    """Every operation of the scores, the selection and the attention
    under the mask carries its named scope BELOW ``cdt.llm_attn`` (sixteen
    device layers there are: ``telemetry/device_scopes.py``)."""
    import re

    text = jax.jit(lambda i: G.prefill(CFG, params, i, T + NEW)).lower(
        ids[:T]).compile().as_text()
    for scope in ("llm_index", "llm_select", "llm_sparse_attn"):
        assert re.search(r"cdt\.llm_attn/(while/body/closed_call/)?"
                         + scope + "/", text), scope
    step = jax.jit(lambda c, t: G.decode_step(CFG, params, c, t, T)).lower(
        G.empty_cache(CFG, T + NEW), ids[T]).compile().as_text()
    for scope in ("llm_index", "llm_sparse_attn"):
        assert f"cdt.llm_attn/{scope}/" in step, scope
    # the reader's pattern finds each and no other component
    from cdtbench.kinds.glm import SCOPES

    part = re.compile(r"/(" + "|".join(SCOPES) + r")(?:/|$)")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert {part.search(n).group(1) for n in names if part.search(n)} \
        == set(SCOPES)


# --- the benchmark's files --------------------------------------------------------


def _cell():
    import cdtbench.workload as workload

    return workload.assemble(CELL)


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "glm-5.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "glm" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    shared = [k for k in fields if k in held]
    assert len(shared) >= 26
    for key in shared:
        assert held[key] == fields[key], key
    assert held["rope_parameters"]["rope_theta"] == fields["rope_theta"]
    # the published widths, unchanged
    assert [held[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_topk", "intermediate_size",
        "moe_intermediate_size", "router_experts", "num_experts_per_tok",
        "routed_scaling_factor")] == [
            6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 12288, 2048,
            256, 8, 2.5]
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == G.param_count(preset.llm)
    assert held["llm"]["bytes"] == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in
        jax.tree_util.tree_leaves(G.init_glm(preset.llm, None,
                                             abstract=True)))
    assert sum(n * (4 if "each of 4" in part else 1) for part, n in
               held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert held["llm"]["cache_bytes_at_65664_positions"] \
        == llm_model.cache_bytes(preset.llm.model, preset.llm, 65664)
    assert "32 chips share each layer" in held["deployment"]
    assert held["router_experts"] == held["published"]["n_routed_experts"] \
        == 32 * held["n_routed_experts"]
    assert held["published"]["vocab_size"] == 8 * held["vocab_size"]
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    assert held["serve_env"] == {}
    assert set(held["reduced"]) == set(held["reduced_why"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "glm-5")
    assert entry["reduced"] == held["reduced"]
    assert entry["source"] == held["source"]
    # every number of the catalog's config, under its key, but the reduced
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"name": "GLM-5"' in line)
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in held["reduced"]:
                assert held[key] == value, key
            else:
                assert held["published"][key] == value, key


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_glm_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_glm_reference.py").read_bytes()
    assert repo == copy


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench.kinds.glm import request_sizes

    cell = _cell()
    assert cell.preset == "glm-5" and cell.chips == 1
    assert request_sizes(cell) == (65536, 128)
    assert (cell.steps, cell.cfg, cell.image_hw) == (8, 6.0, (1024, 1024))
    names = {m["name"] for m in cell.metrics("per_layer")}
    mine = {n for n in names if n.startswith("glm_")}
    assert len(mine) == 12
    assert not {n for n in names if n.startswith(("kimi_", "sala_"))}
    small = __import__("cdtbench.workload").workload.assemble(
        CELL, rehearsal=True)
    assert small.preset == "glm-tiny" and request_sizes(small) == (40, 16)


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    from cdtbench.kinds import glm

    config = _cell().config
    assert glm.parameters(config) == 2_701_673_216
    assert glm.cache_bytes_per_token(config) == 1408
    assert glm.selected_pairs(config, 0, 65536) == 132_121_600
    n = 65536 + 128
    assert 100 * glm.selected_pairs(config, 0, n) / (n * (n + 1) / 2) \
        == pytest.approx(6.14, abs=0.01)
    assert glm.index_score_flops(config, 65536) == pytest.approx(
        5 * 17.6e12, rel=2e-3)
    pairs = 5 * glm.selected_pairs(config, 0, 65536)
    assert glm.selected_pair_flops(config, pairs) == pytest.approx(
        5 * 132.1216e6 * 64 * 512 * 2)
    assert glm.selected_pair_flops(config, pairs, absorbed=True) \
        == pytest.approx(92.0e12, rel=2e-3)
    even = 65536 * 8 * 4 * 8 / 256             # held slots, routing even
    total = glm.prefill_flops(config, 65536, pairs, even)
    assert total == pytest.approx(349e12, rel=5e-3)     # the issue's ~349
    assert glm.prefill_flops(config, 65536, pairs, even + 1) - total \
        == pytest.approx(2 * 3 * 6144 * 2048)
    cfg = G.GlmConfig.glm_share()
    tree = G.init_glm(cfg, None, abstract=True)
    fixed = expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        size = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if "e_gu" in name or "e_down" in name:
            expert += size // cfg.num_experts      # ONE expert of each layer
        elif "embed" in name:
            fixed += cfg.hidden_size * leaf.dtype.itemsize     # one row
        else:
            fixed += size
    cache = 5 * ((65536 + 64) * 128 + 2048 * 576) * 2
    want = fixed + cache + 0.03125 * 8 * expert
    got = glm.decode_bytes_per_token(config, 0.03125, 65536, 128)
    assert abs(got - want) / want < 1e-6
    assert 2.80e9 < got < 2.95e9         # the issue's 2.83 GB + the caches


def _snapshot(held, absent, seconds, requests):
    def slots(where, phase, value):
        return {"labels": {"where": where, "phase": phase}, "value": value}

    pairs = G.GlmConfig.glm_share().attended_keys(65536, 128)
    return {
        "cdt_llm_expert_slots_total": {"series": [
            slots("held", "decode", held), slots("absent", "decode", absent),
            slots("held", "prefill", 512 * held),
            slots("absent", "prefill", 512 * absent)]},
        "cdt_llm_attn_keys_total": {"series": [
            {"labels": {"layers": "sparse", "phase": phase},
             "value": requests * n} for (_, phase), n in pairs.items()]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 10 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock():
    from cdtbench import readers
    from cdtbench.kinds import glm

    cell = _cell()
    slots = 2 * 128 * 32                       # two requests' decode slots
    held = slots // 32
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 12.0}] * 2,
           "opened": _snapshot(10, 90, 1.0, 1),
           "closed": _snapshot(10 + held, 90 + slots - held,
                               1.0 + 2 * 0.64, 3),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 10.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 0.6, "count": 1},
                         "llm_prefill": {"seconds": 8.0, "count": 1}},
                     "op_seconds": {"index_score_sums.1": 0.5,
                                    "index_score_sums.2": 0.3,
                                    "index_select_keep.5": 0.4,
                                    "index_masked_mha.3": 5.0,
                                    "fusion.7": 1.0}}}
    config = cell.config
    assert readers.read("glm_decode_ms_per_token", ctx) == pytest.approx(5.0)
    assert readers.read("glm_prefill_ms", ctx) == pytest.approx(6400.0)
    assert readers.read("glm_share_pct", ctx) == pytest.approx(
        100 * 11 * 1.28 / 24.0)
    need = glm.decode_bytes_per_token(config, 1 / 32, 65536, 128)
    assert readers.read("glm_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / (0.6 / 128), rel=1e-6)
    pairs = 5 * glm.selected_pairs(config, 0, 65536)
    flops = glm.prefill_flops(config, 65536, pairs, 512 * held / 2)
    assert readers.read("glm_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 8.0, rel=1e-6)
    assert readers.read("glm_index_mxu_pct", ctx) == pytest.approx(
        100 * glm.index_score_flops(config, 65536) / 197e12 / 0.8, rel=1e-6)
    assert readers.read("glm_sparse_core_mxu_pct", ctx) == pytest.approx(
        100 * glm.selected_pair_flops(config, pairs) / 197e12 / 5.0,
        rel=1e-6)
    assert readers.read("glm_sparse_core_mxu_pct", ctx) < 100
    assert readers.read("glm_sparse_core_pct", ctx) == pytest.approx(50.0)
    assert readers.read("glm_selected_keys_pct", ctx) == pytest.approx(
        6.14, abs=0.01)
    assert readers.read("glm_held_slot_pct", ctx) == pytest.approx(100 / 32)
    # no trace, a trace without the kernels (the lax forms shipped, or the
    # parent), or a program without the series: nothing, not zero
    for name in ("glm_decode_hbm_pct", "glm_prefill_mfu_pct",
                 "glm_index_mxu_pct", "glm_sparse_core_mxu_pct",
                 "glm_sparse_core_pct", "glm_index_pct", "glm_select_pct"):
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    for name in ("glm_index_mxu_pct", "glm_sparse_core_mxu_pct",
                 "glm_sparse_core_pct"):
        assert readers.read(name, {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("glm_decode_hbm_pct", "glm_decode_ms_per_token",
                 "glm_prefill_ms", "glm_held_slot_pct", "glm_share_pct",
                 "glm_selected_keys_pct", "glm_prefill_mfu_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of them
    import cdtbench.workload as workload

    kimi = workload.assemble("kimi-k2.6.brief32k-sdxl8")
    for name in ("glm_decode_hbm_pct", "glm_decode_ms_per_token",
                 "glm_share_pct", "glm_prefill_mfu_pct",
                 "glm_index_mxu_pct", "glm_sparse_core_mxu_pct",
                 "glm_selected_keys_pct", "glm_index_pct"):
        assert readers.read(name, {**ctx, "cell": kimi}) is None, name


def test_the_scope_reader_sums_self_times_by_named_scope(monkeypatch,
                                                         tmp_path):
    from cdtbench import device_layers as dl
    from cdtbench.kinds import glm

    cell = _cell()
    stacks = {1: "jit(llm_prefill)/cdt.llm_attn/llm_index/pallas_call",
              2: "jit(llm_prefill)/cdt.llm_attn/llm_select/pallas_call",
              3: "jit(llm_prefill)/cdt.llm_attn/llm_sparse_attn/dot",
              4: "jit(llm_prefill)/cdt.llm_attn/dot_general",
              5: "jit(llm_decode)/cdt.llm_attn/llm_index/top_k"}
    plane = {"lines": {dl.OPS_LINE: "events"},
             "metadata": {k: k for k in stacks}}
    monkeypatch.setattr(dl, "find_xplane", lambda d: tmp_path / "t.xplane.pb")
    monkeypatch.setattr(dl, "_key", lambda p: ("t", 1))
    monkeypatch.setattr(dl, "read_space", lambda p: [plane])
    monkeypatch.setattr(dl, "describe", lambda k: {
        "tf_op": stacks[k], "control_flow": False})
    monkeypatch.setattr(dl, "self_times", lambda line: [
        (1, 2e9), (2, 1e9), (3, 5e9), (4, 1e9), (5, 0.5e9)])
    glm._scope_seconds.clear()
    ctx = {"cell": cell, "trace": {"busy_s": 10.0 * dl.PS / 1e-9 * 1e9}}
    found = glm.scope_seconds(ctx)
    assert found["llm_index"] / found["llm_select"] == pytest.approx(2.5)
    assert found["llm_sparse_attn"] / found["llm_select"] \
        == pytest.approx(5.0)
    assert glm.scope_pct(ctx, "llm_index") == pytest.approx(25.0)
    assert glm.scope_pct(ctx, "llm_select") == pytest.approx(10.0)
    glm._scope_seconds.clear()


def test_the_parity_tool_rehearses_and_its_reference_is_the_repos(
        capsys, monkeypatch, tmp_path):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_glm

    assert parity_glm.load_reference().forward.__doc__ == R.forward.__doc__
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "glm-5.parity.json").read_text())
    assert set(limits["limits"]) == {"best_decode_row_rel_l2",
                                     "median_row_rel_l2", "worst_row_rel_l2"}
    assert set(limits["selection_limits"]) == {"gap_median", "keys_off_a_query"}
    assert all(v["limit"] > 0 and v["reason"]
               for v in limits["selection_limits"].values())
    assert all(0 < v["limit"] < 0.1 and v["reason"]
               for v in limits["limits"].values())
    monkeypatch.setattr(parity_glm.W, "ROOT", tmp_path)
    rc = parity_glm.main(["--workload", CELL, "--rehearse", "--degrade",
                          "none,no_relu,top1024"])
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and said["inside_tolerances"], said["faults"]
    readings = said["readings"]
    none = next(v for k, v in readings.items() if k.endswith(".none"))
    # float32 here: the model IS the reference given its selections, and
    # its selections are the reference's; an arm's are not
    assert none["given_selections"]["worst_row_rel_l2"] < 1e-5
    assert none["selections"]["agree_pct"] == 100.0
    assert none["walk_vs_served_rel_l2"] < 1e-5
    for arm in ("no_relu", "top1024"):
        low = next(v for k, v in readings.items() if k.endswith("." + arm))
        assert low["faults"] and low["selections"]["agree_pct"] < 100.0
        assert low["given_selections"]["worst_row_rel_l2"] > 1e-2


@pytest.mark.parametrize("arm", ["cache_fp8", "index_fp8", "scores_bf16",
                                 "no_relu", "no_index_rope", "top1024"])
def test_the_parity_tools_arms_change_what_the_program_computes(params, ids,
                                                               arm):
    """Each arm, built around the served functions while they are traced,
    moves the logits or the selection; outside the context the served
    functions are back."""
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_glm

    def run(cfg):
        return G.prefill_chunk(cfg, params, G.empty_cache(cfg, 32),
                               ids[:32], 0, 32, keep_masks=True)

    logits, _, _, _, masks = run(CFG)
    cfg = parity_glm.cfg_of(CFG, arm)
    assert (cfg.index_topk == 6) == (arm == "top1024")
    kept = (ops.index_scores, ops.masked_chunk_attention, G._index_in)
    with parity_glm.lowered(arm):
        low, _, _, _, low_masks = run(cfg)
    assert (ops.index_scores, ops.masked_chunk_attention, G._index_in) == kept
    moved = not np.array_equal(np.asarray(logits), np.asarray(low))
    reselected = any(not np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(masks, low_masks))
    # rounding 32 scores to bfloat16 need not move the 12th place
    assert moved or arm == "scores_bf16"
    if arm in ("no_relu", "no_index_rope", "top1024"):
        assert reselected
    again = run(CFG)[0]
    assert np.array_equal(np.asarray(again), np.asarray(logits))


def test_the_golden_names_a_request_and_holds_an_image():
    from PIL import Image

    spec = json.loads((ROOT / "cdtbench" / "goldens"
                       / f"{CELL}.json").read_text())
    assert spec["request"]["seed"] > 0 and spec["request"]["prompt"]
    assert spec["stride"] == 4 and spec["max_mean_abs_levels"] == 2.0
    image = np.asarray(Image.open(ROOT / "cdtbench" / "goldens"
                                  / f"{CELL}.png"))
    assert image.shape == (256, 256, 3) and image.min() < image.max()
