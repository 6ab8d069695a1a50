"""The ninth prompt rewriter (grouped-query attention over the keys a learned
indexer picks for every query — the K/V rows themselves —, an index-key
cache beside the K/V cache, EVERY expert of the router held) at the tiny
float32 preset, against the plain reference on seeded weights — logits, not
tokens: the chunked prefill and decode through both caches with ``topk``
BELOW the prompt length, the selections against ``lax.top_k``, the new core
in the interpreter against its ``jnp`` form, decode's gathered step, the
expert share, the shared pipeline, the nodes, the shipped graph, and the
benchmark's files, counts and readers of the cell."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_keye as K
from comfyui_distributed_tpu.models import llm_keye_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import expert_share, gqa_attention
from comfyui_distributed_tpu.ops import index_gqa_attention as gqa_ops
from comfyui_distributed_tpu.ops import index_select_attention as ops

ROOT = Path(__file__).resolve().parent.parent
# a float32 program against the float32 reference: logits of unit scale
# through 2 layers: 1.2e-6 measured; 2e-4 is two orders under what one wrong
# key, a dropped ReLU or a missing norm reads
F32_TOL = 2e-4
CFG = K.KeyeConfig.tiny()
CELL = "keye-vl-2.0-30b-a3b.brief64k-sdxl8"
T, NEW = 40, 6


@pytest.fixture(scope="module")
def params():
    return K.init_keye(CFG, jax.random.key(0))


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
    assert CFG.topk < T                            # the selection bites
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 3
    assert CFG.moe_layers == [0, 1]                # every layer routes
    assert CFG.num_experts == CFG.router_experts   # and holds them all
    assert T > 2 * CFG.prefill_chunk_tokens and T % CFG.prefill_chunk_tokens
    assert CFG.routing == expert_share.Routing(8, 2, 1, 1, 1.0,
                                               score="softmax")
    full = K.KeyeConfig.keye_share()
    assert full.index_weight_scale == pytest.approx(16 ** -0.5 * 64 ** -0.5)
    assert full.routing == expert_share.Routing(128, 8, 1, 1, 1.0,
                                                score="softmax")
    assert (full.num_experts, full.first_expert) == (128, 0)
    assert full.head_dim // full.indexer_head_dim == 2


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("chunk", [16, 8, 10, T])
def test_chunked_prefill_is_the_reference_at_every_position(
        params, ids, full_logits, kernel, chunk):
    """Across chunk boundaries, with a padded last chunk (16, 10) and
    whole; every position reads only its 12 keys."""
    logits, cache, held = K.prefill(CFG, params, ids[:T], T + NEW,
                                    all_logits=True, chunk=chunk,
                                    kernel=kernel)
    assert close(logits, full_logits[:T])
    assert held.tolist() == [T * CFG.num_experts_per_tok] * 2
    rows = max(T + NEW, -(-T // chunk) * chunk)
    assert cache["ki"][0].shape == (-(-rows // 16) * 16,
                                    CFG.indexer_head_dim)
    assert cache["kv"][0].shape[1] == 2 * 2 * CFG.head_dim


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        params, ids, full_logits):
    logits, cache, _ = K.prefill(CFG, params, ids[:T], T + NEW)
    assert close(logits, full_logits[T - 1])
    step = jax.jit(lambda c, t, p: K.decode_step(CFG, params, c, t, p))
    for j in range(T, T + NEW):
        logits, cache, held = step(cache, ids[j], j)
        assert close(logits, full_logits[j]), j
        assert held.tolist() == [CFG.num_experts_per_tok] * 2


def test_with_topk_past_the_prompt_it_is_dense_grouped_query_attention(
        params, ids):
    """``topk ≥ T``: every query keeps its whole prefix, and the model is
    the reference GIVEN the causal mask — plain grouped-query attention,
    which ``gqa_attention.causal_chunk`` computes."""
    dense = dataclasses.replace(CFG, topk=64)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    want = R.forward(dense, params, ids[:T], given=lambda i, lo, n:
                     causal[lo:lo + n])[0]
    got = K.prefill(dense, params, ids[:T], T, all_logits=True)[0]
    assert close(got, want)
    assert close(R.forward(dense, params, ids[:T])[0], want)
    assert not close(K.prefill(CFG, params, ids[:T], T,
                               all_logits=True)[0], want)
    keys = jax.random.split(jax.random.key(2), 2)
    q = jax.random.normal(keys[0], (16, 6, 8))
    kv = jax.random.normal(keys[1], (48, 2 * 2 * 8))
    k, v = (jnp.swapaxes(a.reshape(48, 2, 8), 0, 1)
            for a in jnp.split(kv, 2, axis=1))
    keep = (16 + jnp.arange(16)[:, None] >= jnp.arange(48)[None, :])
    for kernel in ("lax", "interpret"):
        mine = gqa_ops.masked_chunk_gqa(q, kv, keep.astype(jnp.int8), 16, 2,
                                        0.3, jnp.float32, kernel, (8, 16))
        theirs = gqa_attention.causal_chunk(q, k, v, 16, 0.3, jnp.float32,
                                            8, 16, kernel="lax")
        assert np.allclose(np.asarray(mine), np.asarray(theirs), atol=1e-5)


def test_the_reference_in_query_blocks_and_given_a_selection_is_itself(
        params, ids, full_logits):
    blocked, held = R.forward(CFG, params, ids, block=16)
    assert close(blocked, full_logits, 1e-5)
    assert [int(h) for h in held] == [(T + NEW) * 2] * 2
    taps = {}
    R.forward(CFG, params, ids, block=16,
              tap=lambda i, lo, s: taps.setdefault(i, []).append(s))
    own = [R.select(jnp.concatenate(taps[i]), CFG.topk)
           for i in range(CFG.num_hidden_layers)]
    assert all(int(m[t].sum()) == min(CFG.topk, t + 1)
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
    got = K.prefill(low, params, ids[:T], T + NEW, all_logits=True)[0]
    assert not close(got, full_logits[:T])


@pytest.mark.parametrize("norm", ["q_norm", "k_norm"])
def test_the_per_head_norms_are_in_the_program(params, ids, full_logits,
                                               norm):
    """A per-head norm weight that is not 1 moves the logits, and moves
    them as the reference's does."""
    other = {**params, "layers": [
        {**layer, "attn": {**layer["attn"], norm: layer["attn"][norm] * 1.5}}
        for layer in params["layers"]]}
    got = K.prefill(CFG, other, ids[:T], T, all_logits=True)[0]
    assert close(got, R.forward(CFG, other, ids[:T])[0])
    assert not close(got, full_logits[:T])


def test_the_index_heads_read_every_second_column_of_the_rope_table():
    """A 128-wide head's pair ``k`` turns by ``θ^(−k/64)``, a 64-wide index
    head's pair ``i`` by ``θ^(−i/32)``: column ``2i`` of the same table."""
    cfg = K.KeyeConfig.keye_share()
    cfg = dataclasses.replace(cfg, max_position_embeddings=512)
    table = K.rope_table(cfg)
    cos, sin = K._index_rope(cfg, (table["cos"][300:304],
                                   table["sin"][300:304]))
    want_cos, want_sin = R.rope_angles(cfg, 304, cfg.indexer_head_dim)
    assert cos.shape == (4, 32)
    assert np.allclose(np.asarray(cos), np.asarray(want_cos[300:]),
                       atol=1e-6)
    assert np.allclose(np.asarray(sin), np.asarray(want_sin[300:]),
                       atol=1e-6)
    head_cos, _ = R.rope_angles(cfg, 304, cfg.head_dim)
    assert np.allclose(np.asarray(table["cos"][:304]), np.asarray(head_cos),
                       atol=1e-6)


# --- the selection -------------------------------------------------------------


def test_the_models_selections_are_lax_top_ks_position_for_position(params,
                                                                    ids):
    """Every layer's mask of every chunk, at float32: exactly the
    reference's own ``lax.top_k`` of its own scores — ``min(topk, t + 1)``
    keys, the whole prefix below ``topk``."""
    taps = {}
    R.forward(CFG, params, ids[:T], tap=lambda i, lo, s: taps.setdefault(i, s))
    want = [np.asarray(R.select(taps[i], CFG.topk))
            for i in range(CFG.num_hidden_layers)]
    cache = K.empty_cache(CFG, 48)
    padded = jnp.pad(ids[:T], (0, 48 - T))
    for c in range(3):
        valid = min(16, T - 16 * c)
        _, cache, _, _, masks = K.prefill_chunk(
            CFG, params, cache, padded[16 * c:16 * c + 16], 16 * c, valid,
            keep_masks=True)
        for i, mask in enumerate(masks):
            got = np.asarray(mask)[:valid, :T] != 0
            assert np.array_equal(got, want[i][16 * c:16 * c + valid]), (c, i)
            assert np.array_equal(got.sum(1), np.minimum(
                CFG.topk, 16 * c + np.arange(valid) + 1))


def test_a_forced_tie_goes_to_the_lower_position():
    scores = jnp.asarray([[0.5, 2.0, 1.0, 1.0, 1.0, 0.1, 1.0, 3.0]])
    want = np.asarray(R.select(scores, 4))[0].tolist()
    assert want == [False, True, True, True, False, False, False, True]
    for kernel in ("lax", "interpret"):
        keep = ops.select_keep(scores, 7, 4, kernel)
        assert (np.asarray(keep)[0] != 0).tolist() == want


def test_decode_keeps_the_rows_the_reference_selects(params, ids):
    _, cache, _ = K.prefill(CFG, params, ids[:T], T + NEW)
    taps = {}
    R.forward(CFG, params, ids[:T + 1],
              tap=lambda i, lo, s: taps.setdefault(i, s))
    *_, kept = K.decode_step(CFG, params, cache, ids[T], T, keep_rows=True)
    for i, (rows, valid) in enumerate(kept):
        want = np.flatnonzero(np.asarray(R.select(taps[i], CFG.topk))[T])
        assert bool(valid.all())
        assert sorted(np.asarray(rows).tolist()) == want.tolist(), i


# --- the new core in the interpreter against its jnp form ----------------------


@pytest.mark.parametrize("tile", [(8, 16), (16, 16), (4, 32)])
@pytest.mark.parametrize("start", [0, 32])
def test_the_masked_grouped_kernel_is_a_softmax_over_the_kept_keys(start,
                                                                   tile):
    """ONE mask for all 8 heads of all four groups; K and V read from the
    row buffer where they lie."""
    keys = jax.random.split(jax.random.key(5), 3)
    C, H, G, d, S = 16, 8, 4, 16, 64
    q = jax.random.normal(keys[0], (C, H, d)) / 4
    kv = jax.random.normal(keys[1], (S, 2 * G * d))
    seen = start + np.arange(C)[:, None] >= np.arange(S)[None, :]
    keep = (jax.random.uniform(keys[2], (C, S)) < 0.3) & seen
    keep = keep.at[:, 0].set(True).astype(jnp.int8)
    want = gqa_ops.masked_gqa_lax(q, kv, keep, G, jnp.float32)
    got = gqa_ops.index_masked_gqa(
        q.reshape(C, -1), kv, keep, start, num_heads=H, num_kv_heads=G,
        block_q=tile[0], block_k=tile[1], interpret=True)
    assert np.allclose(np.asarray(got).reshape(C, H, d), np.asarray(want),
                       atol=1e-5)
    # head h reads K/V head h // 2, by hand for one query and one head
    h, t = 5, 3
    k = np.asarray(kv[:, (h // 2) * d:(h // 2 + 1) * d])
    v = np.asarray(kv[:, (G + h // 2) * d:(G + h // 2 + 1) * d])
    s = np.where(np.asarray(keep)[t] != 0, k @ np.asarray(q[t, h]), -np.inf)
    p = np.exp(s - s.max())
    assert np.allclose(np.asarray(want)[t, h], (p / p.sum()) @ v, atol=1e-5)


# --- the step by parts over a traced K extent (PR 65) -------------------------


def _parted_case(start, S, C=256, H=8, G=4, d=16):
    """``C`` queries at ``start …`` of 8 heads over 4 K/V heads of 16 against
    a cache of ``S`` rows under a random causal mask that keeps about a
    third — and ONE key only in row 5; rows past ``start + C`` hold NaN (a
    kernel that multiplied a tile past the chunk would answer it)."""
    keys = jax.random.split(jax.random.key(65), 3)
    q = jax.random.normal(keys[0], (C, H, d)) / 4
    kv = jax.random.normal(keys[1], (S, 2 * G * d))
    kv = kv.at[-(-(start + C) // 256) * 256:].set(jnp.nan)
    seen = start + np.arange(C)[:, None] >= np.arange(S)[None, :]
    keep = (jax.random.uniform(keys[2], (C, S)) < 0.3) & seen
    keep = keep.at[:, 0].set(True).at[5].set(False).at[5, start + 2].set(True)
    return q, kv, keep.astype(jnp.int8)


def _parted(q, kv, keep, start, tile, part, k_steps=None):
    C, H, d = q.shape
    bq, bk = tile
    if k_steps is None:
        k_steps = gqa_ops.core_k_steps(jnp.int32(start), C, bk,
                                       kv.shape[0] // bk)
    return np.asarray(gqa_ops.masked_gqa_call(
        q.reshape(C, H * d), kv, keep, start, k_steps, H, 4, bq, bk, part,
        True)).reshape(C, H, d)


# the diagonal inside a query tile's K tile (start 160 + 256 rows end at 415:
# K tiles of 128 and of 256 are crossed mid-tile)
@pytest.mark.parametrize("tile", [(256, 128), (256, 256)])
@pytest.mark.parametrize("part", [256, 128, 64, 32])
def test_a_steps_rows_in_parts_answer_the_whole_heads_bits(part, tile):
    """``part`` rows of one head a logit product, heads outer, the next
    part's product traced ahead of a part's softmax: each row still takes
    ONE softmax over the whole K tile a step, so the answers are the whole
    head's to the bit — and the plain masked softmax inside the file's
    tolerance, in a row that keeps one key too."""
    start = 160
    q, kv, keep = _parted_case(start, 512)
    whole = _parted(q, kv, keep, start, tile, tile[0])
    got = _parted(q, kv, keep, start, tile, part)
    assert np.array_equal(got, whole)
    want = np.asarray(gqa_ops.masked_gqa_lax(
        q, jnp.nan_to_num(kv), keep, 4, jnp.float32))
    assert np.allclose(got, want, atol=1e-5)
    # the row that keeps one key answers that key's value, every head
    v = np.asarray(kv[start + 2, 4 * 16:]).reshape(4, 16)
    assert np.allclose(got[5], np.repeat(v, 2, axis=0), atol=1e-6)


@pytest.mark.parametrize("start", [0, 768, 1792])
def test_the_grid_ends_where_the_chunk_sees_whatever_pads_the_cache(
        start, monkeypatch):
    """A cache padded far past ``start + C`` (the first, a middle and the
    last chunk of a prompt of 2048 in a buffer of 2560: 4 to 18 dead K
    blocks of 128) answers the bits of a cache that ends at the chunk; the
    jitted call's grid carries ONE traced bound, its K axis, and that bound
    reads ``core_k_steps(start, C, block_k, nk)``."""
    C, S, tile = 256, 2560, (128, 128)
    q, kv, keep = _parted_case(start, S)
    ends = start + C
    got = gqa_ops.index_masked_gqa(
        q.reshape(C, -1), kv, keep, start, num_heads=8, num_kv_heads=4,
        block_q=tile[0], block_k=tile[1], interpret=True)
    short = gqa_ops.index_masked_gqa(
        q.reshape(C, -1), kv[:ends], keep[:, :ends], start, num_heads=8,
        num_kv_heads=4, block_q=tile[0], block_k=tile[1], interpret=True)
    assert (S - ends) // tile[1] >= 4
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(short))
    # traced: one dynamic grid bound
    jaxpr = jax.make_jaxpr(lambda *a: gqa_ops.index_masked_gqa.__wrapped__(
        *a, 8, 4, *tile, False))(q.reshape(C, -1), kv, keep,
                                 jnp.int32(start))
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["grid_mapping"].num_dynamic_grid_bounds == 1
    # concrete: the bound the call was handed, as a number
    grids = []

    def fake_call(kernel, grid_spec, out_shape, **kw):
        grids.append(grid_spec.grid)
        return lambda *a: jnp.zeros(out_shape.shape, out_shape.dtype)

    monkeypatch.setattr(gqa_ops.pl, "pallas_call", fake_call)
    gqa_ops.index_masked_gqa.__wrapped__(
        q.reshape(C, -1), kv, keep, jnp.int32(start), 8, 4, *tile, False)
    G, nq, steps = grids[0]
    nk = S // tile[1]
    assert (G, nq) == (4, C // tile[0])
    assert int(steps) == int(gqa_ops.core_k_steps(start, C, tile[1], nk)) \
        == -(-ends // tile[1]) <= nk - 4


@pytest.mark.parametrize("block_q", [192, 96, 32])
def test_a_tile_the_part_does_not_divide_is_taken_a_head_at_a_time(
        block_q, monkeypatch):
    """``core_part``'s rule (``step_rows``' shape, this tile's length): 64
    rows or the whole tile, by the tile alone — and the served call hands
    its kernel what the rule says of ITS tile."""
    assert gqa_ops.core_part(gqa_ops.CORE_TILE[0]) == gqa_ops.CORE_PART == 64
    part = gqa_ops.core_part(block_q)
    assert part == (64 if block_q == 192 else block_q)
    C = 2 * block_q
    q, kv, keep = _parted_case(64, 512, C=C)
    parts = []
    call = gqa_ops.masked_gqa_call
    monkeypatch.setattr(gqa_ops, "masked_gqa_call",
                        lambda *a: parts.append(a[7:10]) or call(*a))
    got = gqa_ops.index_masked_gqa(
        q.reshape(C, -1), kv, keep, 64, num_heads=8, num_kv_heads=4,
        block_q=block_q, block_k=128, interpret=True)
    assert parts == [(block_q, 128, part)]
    want = gqa_ops.masked_gqa_lax(q, jnp.nan_to_num(kv), keep, 4,
                                  jnp.float32)
    assert np.allclose(np.asarray(got).reshape(want.shape), np.asarray(want),
                       atol=1e-5)


def test_the_site_reports_its_part_in_the_attention_line(monkeypatch):
    """``masked_chunk_gqa`` on a TPU notes the tile AND ``core_part`` of it:
    ``512/2048/64`` in the ``attention:`` line and the counter's ``blocks``
    label at the served sizes, a tile the rule leaves whole as it always
    read."""
    from comfyui_distributed_tpu.ops import attention

    monkeypatch.setattr(gqa_ops, "index_masked_gqa", lambda q, *a, **kw: q)
    attention.reset_selections()
    for C, S in ((4096, 69632), (16, 64)):
        gqa_ops.masked_chunk_gqa(
            jnp.zeros((C, 32, 128), jnp.bfloat16),
            jnp.zeros((S, 8), jnp.bfloat16), None, 0, 4, 1.0, jnp.bfloat16,
            "pallas")
    summary = attention.selection_summary()
    assert "index_select:512/2048/64" in summary
    assert "index_select:16/64" in summary and "16/64/" not in summary
    attention.reset_selections()


@pytest.mark.parametrize("k_part", [64, 128])
def test_the_sweeps_losing_arm_is_the_same_attention(k_part, monkeypatch):
    """``scripts/keye_sweep.py`` times PR 60's form — the K tile in parts —
    through this module's call with a kernel body of its own: the same
    softmax, or the table compares different work."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "keye_sweep", ROOT / "scripts" / "keye_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    start = 160
    q, kv, keep = _parted_case(start, 512)
    want = _parted(q, kv, keep, start, (256, 128), 256)
    monkeypatch.setattr(gqa_ops, "_masked_gqa_kernel",
                        sweep.k_parts_kernel(k_part))
    got = _parted(q, kv, keep, start, (256, 128), 256)
    assert np.allclose(got, want, atol=1e-5)
    forms = sweep.core_forms([(512, 2048), (256, 2048)],
                             ["whole", "256", "128"], [512], ["chunk"],
                             gqa_ops.CORE_TILE)
    assert [sweep.core_label(*f) for f in forms] == [
        "512x2048", "512x2048/256", "512x2048/128", "256x2048",
        "256x2048/128", "512x2048/k512"]
    # 16c + 12 visible steps a K/V head a layer at the shipped tile
    assert [sweep.visible_steps(c, 512, 2048) for c in (0, 7, 15)] \
        == [12, 124, 252]
    assert sum(sweep.visible_steps(c, 512, 2048) for c in range(16)) == 2112


def test_the_score_kernel_at_this_geometry_is_the_plain_sum():
    kq, kw, kk = jax.random.split(jax.random.key(4), 3)
    C, J, d, S = 16, 4, 8, 64
    q_i = jax.random.normal(kq, (C, J, d))
    w = jax.random.normal(kw, (C, J))
    k_i = jax.random.normal(kk, (S, d))
    want = gqa_ops.index_scores(q_i, w, k_i, 16, jnp.float32, "lax")
    got = gqa_ops.index_scores(q_i, w, k_i, 16, jnp.float32, "interpret",
                               (8, 16))
    seen = 16 + np.arange(C)[:, None] >= np.arange(S)[None, :]
    assert np.allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                       atol=1e-5)


def test_decodes_gathered_step_is_the_masked_step_over_the_whole_cache():
    keys = jax.random.split(jax.random.key(7), 2)
    H, G, d, S = 6, 2, 8, 32
    q = jax.random.normal(keys[0], (H, d))
    kv = jax.random.normal(keys[1], (S, 2 * G * d))
    rows = jnp.asarray([3, 30, 11, 0, 17, 5])
    valid = jnp.asarray([True, True, True, True, False, True])
    k, v = (jnp.swapaxes(a.reshape(S, G, d), 0, 1)
            for a in jnp.split(kv, 2, axis=1))
    mask = jnp.zeros((S,), bool).at[rows[valid]].set(True)
    want = gqa_attention.step(q, k, v, mask, 0.25, jnp.float32)
    got = gqa_ops.gathered_step(q, kv, rows, valid, G, 0.25, jnp.float32)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    other = gqa_ops.gathered_step(q, kv, rows, jnp.ones((6,), bool), G, 0.25,
                                  jnp.float32)
    assert not np.allclose(np.asarray(other), np.asarray(want), atol=1e-3)


# --- the expert share ------------------------------------------------------------


def test_the_parts_of_four_quarter_shares_add_up_to_the_all_held_layer():
    """8 experts over 4 chips: every share routes over all 8 and computes
    its own two; the parts add up to what the module gives holding ALL
    (in each form), which is the uncut reference layer."""
    m = K.init_keye(CFG, jax.random.key(8))["layers"][1]["moe"]
    x = jax.random.normal(jax.random.key(10), (9, CFG.hidden_size))
    want, want_held = R.experts(CFG, m, x)
    idx, w = expert_share.route(x, m["w_router"], None, CFG.routing)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    whole, rows = expert_share.held_part(
        x, idx, w, m["e_gu"], m["e_down"], 0, jnp.float32, CFG.routing,
        tile=CFG.expert_tile)
    assert close(whole, want) and int(rows) % CFG.expert_tile == 0
    token = jnp.stack([expert_share.held_part_token(
        x[t], idx[t], w[t], m["e_gu"], m["e_down"], 0, jnp.float32)
        for t in range(9)])
    assert close(token, want, 1e-5)
    total, held = 0.0, 0
    for first in range(0, 8, 2):
        share = {k: m[k][first:first + 2] for k in ("e_gu", "e_down")}
        dense = expert_share.held_part_dense(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32)
        grouped, _ = expert_share.held_part_grouped(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32,
            tile=2)
        assert close(dense, grouped, 1e-5)
        total = total + grouped
        held += int(expert_share.held_slots(idx, first, 2).sum())
    assert close(total, want) and close(total, whole, 1e-5)
    assert held == int(want_held) == 9 * CFG.num_experts_per_tok
    # a quarter share through the MODEL leaves the other three out
    quarter = dataclasses.replace(CFG, num_experts=2, first_expert=2)
    part, part_held = R.experts(quarter, {
        **m, "e_gu": m["e_gu"][2:4], "e_down": m["e_down"][2:4]}, x)
    assert not close(part, want) and int(part_held) < int(want_held)


@pytest.mark.parametrize("case", ["all held", "padded chunk", "skewed",
                                  "a quarter share"])
def test_the_streamed_form_is_the_grouped_loop(case):
    """``held_part_streamed`` (ONE kernel over all the tiles, the experts'
    matrices streamed behind a prefetched tile -> expert table) in the
    interpreter against ``held_part_grouped``: the same sum, the same rows
    multiplied; no slot dropped under skew; rows no tile wrote are never
    read (they hold NaN in the interpreter)."""
    keys = jax.random.split(jax.random.key(11), 4)
    n, D, F, E, k, tile = 40, 32, 16, 8, 2, 4
    x = jax.random.normal(keys[0], (n, D))
    e_gu = jax.random.normal(keys[1], (E, D, 2 * F)) / 6
    e_down = jax.random.normal(keys[2], (E, F, D)) / 4
    routing = expert_share.Routing(E, k, 1, 1, 1.0, score="softmax")
    idx, w = expert_share.route(x, jax.random.normal(keys[3], (D, E)), None,
                                routing)
    valid, first = None, 0
    if case == "padded chunk":
        valid = jnp.arange(n) < 33
    elif case == "skewed":                     # every token on experts 0, 1
        idx = jnp.zeros_like(idx).at[:, 1].set(1)
    elif case == "a quarter share":
        first, e_gu, e_down = 2, e_gu[2:4], e_down[2:4]
    want, want_rows = expert_share.held_part_grouped(
        x, idx, w, e_gu, e_down, first, jnp.float32, valid=valid, tile=tile)
    got, rows = expert_share.held_part_streamed(
        x, idx, w, e_gu, e_down, first, jnp.float32, valid=valid, tile=tile,
        kernel="interpret")
    assert bool(jnp.isfinite(got).all())
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert int(rows) == int(want_rows) and int(rows) % tile == 0
    lax, _ = expert_share.held_part_streamed(
        x, idx, w, e_gu, e_down, first, jnp.float32, valid=valid, tile=tile,
        kernel="lax")
    assert np.array_equal(np.asarray(lax), np.asarray(want))


def test_the_streamed_forms_rule_is_one_from_the_shapes():
    """The whole router held AND a whole tile of rows an expert: the ninth
    module's chunk; no share of a wider router, whatever its tile."""
    from comfyui_distributed_tpu.models.registry import PRESETS

    full = K.KeyeConfig.keye_share()
    assert expert_share.streamed_form(4096, 128, full.routing,
                                      full.expert_tile)
    assert not expert_share.streamed_form(4096, 64, full.routing, 256)
    assert not expert_share.streamed_form(1024, 128, full.routing, 256)
    assert expert_share.streamed_form(16, 8, CFG.routing, CFG.expert_tile)
    for name in ("kimi-k2.6", "trinity-large-preview", "longcat-flash-omni",
                 "glm-5", "ling-3.0-flash-vl", "motif-3-beta"):
        cfg = PRESETS[name].llm
        assert not expert_share.streamed_form(
            4096, cfg.num_experts, cfg.routing, expert_share.GROUP_TILE), name
    # by_shape takes the loop where the rule says no, whatever the kernel
    keys = jax.random.split(jax.random.key(12), 3)
    x = jax.random.normal(keys[0], (16, 32))
    e_gu = jax.random.normal(keys[1], (4, 32, 32)) / 6
    e_down = jax.random.normal(keys[2], (4, 16, 32)) / 4
    routing = expert_share.Routing(8, 2, 1, 1, 1.0, score="softmax")
    idx, w = expert_share.route(x, jnp.eye(32, 8), None, routing)
    a, _ = expert_share.held_part_by_shape(x, idx, w, e_gu, e_down, 0,
                                           jnp.float32, routing, tile=4,
                                           kernel="interpret")
    b, _ = expert_share.held_part(x, idx, w, e_gu, e_down, 0, jnp.float32,
                                  routing, tile=4)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_six_older_expert_presets_take_the_form_they_took():
    """``prefill_form`` is one rule from the shapes; what the ninth module
    passes changes none of the older answers (``tests/test_expert_share.py``
    holds their routers)."""
    from comfyui_distributed_tpu.models.registry import PRESETS

    took = {"ling-3.0-flash-vl": (1024, "dense"),
            "motif-3-beta": (1024, "dense"),
            "kimi-k2.6": (32768, "grouped"),
            "trinity-large-preview": (131072, "grouped"),
            "longcat-flash-omni": (16384, "grouped"),
            "glm-5": (65536, "grouped")}
    for name, (prompt, form) in took.items():
        pipe = pipeline_llm.LLMPipeline(PRESETS[name].llm, None)
        assert pipe.prefill_plan(prompt)[2] == form, name
    mine = pipeline_llm.LLMPipeline(K.KeyeConfig.keye_share(), None)
    assert mine.prefill_plan(65536) == (4096, 16, "grouped")
    assert expert_share.GROUP_TILE == 128


def test_the_published_cut_counts_what_the_issue_counted():
    cfg = K.KeyeConfig.keye_share()
    assert K.param_count(cfg) == 3_123_858_944
    tree = K.init_keye(cfg, None, abstract=True)
    layer = tree["layers"][1]
    per_layer = sum(math.prod(a.shape)
                    for a in jax.tree_util.tree_leaves(layer))
    assert per_layer == 625_381_760
    attention = sum(math.prod(a.shape) for name, a in layer["attn"].items()
                    if name.startswith("w_"))
    assert attention == 18_874_368
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
        layer["indexer"])) == 2_261_120
    assert layer["moe"]["e_gu"].shape == (128, 2048, 1536)
    assert layer["moe"]["w_router"].shape == (2048, 128)
    assert tree["head"].shape == tree["embed"].shape == (151936, 2048)
    # 2176 B a token a layer, the rows in whole chunks of 4096
    sizes = llm_model.cache_bytes(cfg.model, cfg, 65536 + 128)
    assert sizes == {"kv": 4 * 69632 * 1024 * 2, "index": 4 * 69632 * 64 * 2}
    assert sum(sizes.values()) == 2176 * 4 * 69632
    pairs = cfg.attended_keys(65536, 128)
    assert pairs[("sparse", "prefill")] == 4 * 132_121_600
    assert pairs[("sparse", "decode")] == 4 * 128 * 2048
    brute = sum(min(CFG.topk, t + 1) for t in range(T + NEW))
    assert sum(CFG.attended_keys(T, NEW).values()) \
        == CFG.num_hidden_layers * brute


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_scans_the_continuation_inside_one_labelled_program(
        params, ids, full_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is K.MODEL
    assert pipe.prefill_plan(T) == (16, 3, "grouped")
    prefill, decode = pipe.programs(T, 8)
    logits, cache, held, rows = prefill(ids[:T])
    assert close(logits, full_logits[T - 1])
    assert held.shape == rows.shape == (2,)
    out, taps, slots, finite = decode(logits, cache, jax.random.key(3),
                                      jnp.asarray(0.7, jnp.float32))
    assert out.shape == (8,) and bool(finite)
    assert slots.tolist() == [8 * CFG.num_experts_per_tok] * 2


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    name = "keye-vl-2.0-30b-a3b"
    assert PRESETS["keye-tiny"].kind == PRESETS[name].kind == "llm"
    assert PRESETS[name].llm == K.KeyeConfig.keye_share()
    assert PRESETS[name].llm.model is K.MODEL
    assert PRESETS["keye-tiny"].llm == CFG
    at = list(PRESETS).index(name)
    assert list(PRESETS)[at:at + 2] == [name, "keye-tiny"]
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("keye-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("keye-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("keye-tiny") is bundle


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "keye-tiny"
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
            "slots": {(k, p): tm.LLM_EXPERT_SLOTS.labels(where=k,
                                                         phase=p).value
                      for k in ("held", "absent")
                      for p in ("prefill", "decode")},
            "rows": {f: tm.LLM_EXPERT_ROWS.labels(form=f).value
                     for f in ("grouped", "token")},
            "keys": {p: tm.LLM_ATTN_KEYS.labels(layers="sparse",
                                                phase=p).value
                     for p in ("prefill", "decode")},
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
            # every routed slot is held: none is ever absent
            assert after["slots"][("held", phase)] \
                - before["slots"][("held", phase)] \
                == 3 * tokens * CFG.num_experts_per_tok * 2
            assert after["slots"][("absent", phase)] \
                == before["slots"][("absent", phase)]
        grouped = after["rows"]["grouped"] - before["rows"]["grouped"]
        assert grouped >= 3 * 40 * 2 * 2 and grouped % CFG.expert_tile == 0
        assert after["rows"]["token"] - before["rows"]["token"] \
            == 3 * 8 * 2 * 2
        assert after["chunks"] - before["chunks"] == 3 * 3
        want = CFG.attended_keys(40, 8)
        for phase in ("prefill", "decode"):
            assert after["keys"][phase] - before["keys"][phase] \
                == 3 * want[("sparse", phase)]
        assert tm.LLM_CACHE_POSITIONS.labels().value == 48
        assert tm.LLM_CACHE_BYTES.labels(layers="kv").value \
            == 2 * 48 * 32 * 4
        assert tm.LLM_CACHE_BYTES.labels(layers="index").value \
            == 2 * 48 * 8 * 4


def test_the_three_pieces_are_told_apart_below_the_attention_scope(params,
                                                                   ids):
    """Every operation of the scores, the selection and the attention
    under the mask carries ``llm_glm``'s named scope BELOW
    ``cdt.llm_attn``; the experts' and the router's are device layers."""
    import re

    text = jax.jit(lambda i: K.prefill(CFG, params, i, T + NEW)).lower(
        ids[:T]).compile().as_text()
    for scope in ("llm_index", "llm_select", "llm_sparse_attn"):
        assert re.search(r"cdt\.llm_attn/(while/body/closed_call/)?"
                         + scope + "/", text), scope
    for layer in ("llm_experts", "llm_router", "llm_head"):
        assert f"cdt.{layer}/" in text, layer
    step = jax.jit(lambda c, t: K.decode_step(CFG, params, c, t, T)).lower(
        K.empty_cache(CFG, T + NEW), ids[T]).compile().as_text()
    for scope in ("llm_index", "llm_sparse_attn"):
        assert f"cdt.llm_attn/{scope}/" in step, scope
    # the reader's pattern (kinds/glm.py's, which kinds/keye.py borrows)
    # finds each and no other component
    from cdtbench.kinds.keye import SCOPES

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
                       / "keye-vl-2.0-30b-a3b.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "keye" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    shared = [k for k in fields if k in held]
    assert len(shared) >= 21
    for key in shared:
        assert held[key] == fields[key], key
    sa = held["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]) \
        == (fields["indexer_num_heads"], fields["indexer_head_dim"],
            fields["topk"])
    # the published widths and counts, unchanged
    assert [held[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_experts", "router_experts", "moe_intermediate_size",
        "num_experts_per_tok", "vocab_size", "rope_theta")] == [
            2048, 32, 4, 128, 128, 128, 768, 8, 151936, 1e7]
    assert sa == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048}
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == K.param_count(preset.llm) \
        == 3_123_858_944
    tree = K.init_keye(preset.llm, None, abstract=True)
    rope = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree["rope"]))
    assert held["llm"]["rope_table_bytes"] == rope
    assert held["llm"]["bytes"] + rope == sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree))
    assert sum(n * (4 if "each of 4" in part else 1) for part, n in
               held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert held["llm"]["cache_bytes_at_65664_positions"] \
        == llm_model.cache_bytes(preset.llm.model, preset.llm, 65664)
    assert "no layer is divided" in held["deployment"] \
        and "12 chips as pipeline stages of 4" in held["deployment"]
    assert held["published"]["num_hidden_layers"] == 48
    assert "NOT READ" in " ".join(held["assumed"])
    assert sum("ASSUMED" in line for line in held["assumed"]) >= 3
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    assert held["serve_env"] == {}
    assert set(held["reduced"]) == set(held["reduced_why"]) == {
        "num_hidden_layers", "vision_tower"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == held["reduced"]
    assert entry["source"] == held["source"] and len(entry["source"]) < 200
    # every number of the catalog's config, under its key, but the reduced
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"name": "Keye-VL-2.0-30B-A3B"' in line)
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in held["reduced"]:
                assert held[key] == value, key
            else:
                assert held["published"][key] == value, key


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_keye_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_keye_reference.py").read_bytes()
    assert repo == copy


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench.kinds.keye import request_sizes

    cell = _cell()
    assert cell.preset == "keye-vl-2.0-30b-a3b" and cell.chips == 1
    assert request_sizes(cell) == (65536, 128)
    assert (cell.steps, cell.cfg, cell.image_hw) == (8, 6.0, (1024, 1024))
    names = {m["name"] for m in cell.metrics("per_layer")}
    mine = {n for n in names if n.startswith("keye_")}
    assert len(mine) == 15
    assert not {n for n in names if n.startswith(("kimi_", "glm_", "sala_"))}
    bench = cell.bench
    ours = [m for m in bench["per_layer"] if m["name"].startswith("keye_")]
    assert all(m["workloads"] == [CELL] and m["moves"] == "request_p50_s"
               for m in ours)
    at = bench["per_layer"].index(ours[0])
    assert bench["per_layer"][at:at + 15] == ours    # appended as one run
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["traffic"] == "brief64k-sdxl8"
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].startswith("keye_")}
    assert {m["layer"] for m in ours} <= layers      # no new layer name
    small = __import__("cdtbench.workload").workload.assemble(
        CELL, rehearsal=True)
    assert small.preset == "keye-tiny" and request_sizes(small) == (40, 16)
    # the same mix as the other three 64k rewriters, unedited
    other = __import__("cdtbench.workload").workload.assemble(
        "glm-5.brief64k-sdxl8")
    assert other.traffic == cell.traffic


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    from cdtbench.kinds import keye

    config = _cell().config
    assert keye.parameters(config) == 3_123_858_944
    assert keye.layer_parameters(config) == 625_381_760
    assert keye.attention_params(config) == 18_874_368
    assert keye.indexer_params(config) + 128 == 2_261_120
    assert keye.expert_params(config) == 4_718_592
    assert keye.cache_bytes_per_token(config) == 2176
    assert keye.selected_pairs(config, 0, 65536) == 132_121_600   # 132.12 M
    n = 65536 + 128
    assert 100 * keye.selected_pairs(config, 0, n) / (n * (n + 1) / 2) \
        == pytest.approx(6.14, abs=0.01)
    assert keye.index_score_flops(config, 65536) == pytest.approx(
        4 * 4.40e12, rel=2e-3)
    pairs = 4 * keye.selected_pairs(config, 0, 65536)
    assert keye.selected_pair_flops(config, pairs) == pytest.approx(
        4 * 132.1216e6 * 32 * 256 * 2)
    assert keye.selected_pair_flops(config, pairs) == pytest.approx(
        4 * 2.17e12, rel=5e-3)
    slots = 65536 * 8 * 4
    assert keye.expert_flops(config, slots) == pytest.approx(4 * 4.95e12,
                                                             rel=2e-3)
    total = keye.prefill_flops(config, 65536, pairs, slots)
    assert total == pytest.approx(57e12, rel=2e-2)        # the issue's ~57
    assert keye.prefill_flops(config, 65536, pairs, slots + 1) - total \
        == pytest.approx(9_437_184)
    cfg = K.KeyeConfig.keye_share()
    tree = K.init_tree(K._shapes(cfg), None, abstract=True)
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
    cache = 4 * ((65536 + 64) * 64 + 2048 * 1024) * 2
    want = fixed + cache + 1.0 * 8 * expert
    got = keye.decode_bytes_per_token(config, 1.0, 65536, 128)
    assert abs(got - want) / want < 1e-6
    assert got == pytest.approx(1.146e9, rel=3e-3)       # the issue's 1.146 GB


def _snapshot(held, seconds, requests, rows=0):
    def slots(where, phase, value):
        return {"labels": {"where": where, "phase": phase}, "value": value}

    pairs = K.KeyeConfig.keye_share().attended_keys(65536, 128)
    return {
        "cdt_llm_expert_slots_total": {"series": [
            slots("held", "decode", held), slots("absent", "decode", 0),
            slots("held", "prefill", 512 * held),
            slots("absent", "prefill", 0)]},
        "cdt_llm_expert_rows_total": {"series": [
            {"labels": {"form": "grouped"}, "value": rows},
            {"labels": {"form": "token"}, "value": held}]},
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


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock(
        monkeypatch):
    from cdtbench import device_layers, readers
    from cdtbench.kinds import keye

    cell = _cell()
    slots = 2 * 128 * 32                       # two requests' decode slots
    report = {"layers": {}, "phases": {
        "llm_prefill": {"llm_experts": {"seconds": 0.5},
                        "llm_attn": {"seconds": 1.5}},
        "llm_decode": {"llm_experts": {"seconds": 0.1},
                       "llm_head": {"seconds": 0.4}}}}
    monkeypatch.setattr(device_layers, "of_run",
                        lambda ctx: report if ctx.get("trace") else None)
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 5.0}] * 2,
           "opened": _snapshot(10, 1.0, 1, rows=1000),
           "closed": _snapshot(10 + slots, 1.0 + 2 * 0.256, 3,
                               rows=1000 + 1.25 * 512 * slots),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 4.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 0.5, "count": 1},
                         "llm_prefill": {"seconds": 2.5, "count": 1}},
                     "op_seconds": {"index_score_sums.1": 0.2,
                                    "index_score_sums.2": 0.1,
                                    "index_select_keep.5": 0.4,
                                    "index_masked_gqa.3": 1.0,
                                    "fusion.7": 1.0}}}
    config = cell.config
    assert readers.read("keye_decode_ms_per_token", ctx) == pytest.approx(2.0)
    assert readers.read("keye_prefill_ms", ctx) == pytest.approx(2560.0)
    assert readers.read("keye_share_pct", ctx) == pytest.approx(
        100 * 11 * 0.512 / 10.0)
    need = keye.decode_bytes_per_token(config, 1.0, 65536, 128)
    assert readers.read("keye_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / (0.5 / 128), rel=1e-6)
    pairs = 4 * keye.selected_pairs(config, 0, 65536)
    flops = keye.prefill_flops(config, 65536, pairs, 512 * slots / 2)
    assert readers.read("keye_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 2.5, rel=1e-6)
    assert readers.read("keye_index_mxu_pct", ctx) == pytest.approx(
        100 * keye.index_score_flops(config, 65536) / 197e12 / 0.3, rel=1e-6)
    assert readers.read("keye_sparse_core_mxu_pct", ctx) == pytest.approx(
        100 * keye.selected_pair_flops(config, pairs) / 197e12 / 1.0,
        rel=1e-6)
    assert readers.read("keye_sparse_core_mxu_pct", ctx) < 100
    assert readers.read("keye_sparse_core_pct", ctx) == pytest.approx(25.0)
    assert readers.read("keye_selected_keys_pct", ctx) == pytest.approx(
        6.14, abs=0.01)
    assert readers.read("keye_held_slot_pct", ctx) == pytest.approx(100.0)
    assert readers.read("keye_expert_rows_per_slot", ctx) \
        == pytest.approx(1.25)
    assert readers.read("keye_experts_pct", ctx) == pytest.approx(
        100 * 0.6 / 2.5)
    assert readers.read("keye_experts_mxu_pct", ctx) == pytest.approx(
        100 * keye.expert_flops(config, 512 * slots / 2) / 197e12 / 0.5,
        rel=1e-6)
    # no trace, a trace without the kernels (the lax forms shipped, or the
    # parent), or a program without the series: nothing, not zero
    for name in ("keye_decode_hbm_pct", "keye_prefill_mfu_pct",
                 "keye_index_mxu_pct", "keye_sparse_core_mxu_pct",
                 "keye_sparse_core_pct", "keye_index_pct", "keye_select_pct",
                 "keye_experts_pct", "keye_experts_mxu_pct"):
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    for name in ("keye_index_mxu_pct", "keye_sparse_core_mxu_pct",
                 "keye_sparse_core_pct"):
        assert readers.read(name, {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("keye_decode_hbm_pct", "keye_decode_ms_per_token",
                 "keye_prefill_ms", "keye_held_slot_pct", "keye_share_pct",
                 "keye_selected_keys_pct", "keye_prefill_mfu_pct",
                 "keye_expert_rows_per_slot", "keye_experts_mxu_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of them
    import cdtbench.workload as workload

    glm = workload.assemble("glm-5.brief64k-sdxl8")
    for name in ("keye_decode_hbm_pct", "keye_decode_ms_per_token",
                 "keye_share_pct", "keye_prefill_mfu_pct",
                 "keye_index_mxu_pct", "keye_sparse_core_mxu_pct",
                 "keye_selected_keys_pct", "keye_index_pct",
                 "keye_experts_pct", "keye_experts_mxu_pct",
                 "keye_expert_rows_per_slot"):
        assert readers.read(name, {**ctx, "cell": glm}) is None, name


def test_the_scope_reader_is_the_index_selecting_kinds(monkeypatch, tmp_path):
    from cdtbench import device_layers as dl
    from cdtbench.kinds import glm, keye

    cell = _cell()
    stacks = {1: "jit(llm_prefill)/cdt.llm_attn/llm_index/pallas_call",
              2: "jit(llm_prefill)/cdt.llm_attn/llm_select/pallas_call",
              3: "jit(llm_prefill)/cdt.llm_attn/llm_sparse_attn/pallas_call",
              4: "jit(llm_prefill)/cdt.llm_experts/dot_general"}
    plane = {"lines": {dl.OPS_LINE: "events"},
             "metadata": {k: k for k in stacks}}
    monkeypatch.setattr(dl, "find_xplane", lambda d: tmp_path / "t.xplane.pb")
    monkeypatch.setattr(dl, "_key", lambda p: ("t", 53))
    monkeypatch.setattr(dl, "read_space", lambda p: [plane])
    monkeypatch.setattr(dl, "describe", lambda k: {
        "tf_op": stacks[k], "control_flow": False})
    monkeypatch.setattr(dl, "self_times", lambda line: [
        (1, 2e9), (2, 1e9), (3, 5e9), (4, 2e9)])
    glm._scope_seconds.clear()
    ctx = {"cell": cell, "trace": {"busy_s": 10.0 * dl.PS / 1e-9 * 1e9}}
    assert keye.scope_pct(ctx, "llm_index") == pytest.approx(20.0)
    assert keye.scope_pct(ctx, "llm_select") == pytest.approx(10.0)
    assert keye.scope_pct({**ctx, "trace": None}, "llm_index") is None
    glm._scope_seconds.clear()


def test_the_parity_tool_rehearses_and_its_reference_is_the_repos(
        capsys, monkeypatch, tmp_path):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_keye

    assert parity_keye.load_reference().forward.__doc__ == R.forward.__doc__
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "keye-vl-2.0-30b-a3b.parity.json").read_text())
    assert set(limits["limits"]) == {"best_decode_row_rel_l2",
                                     "median_row_rel_l2", "worst_row_rel_l2"}
    assert set(limits["selection_limits"]) == {"gap_median",
                                               "keys_off_a_query"}
    assert all(v["limit"] > 0 and len(v["reason"]) > 40
               for v in limits["selection_limits"].values())
    assert all(0 < v["limit"] < 0.1 and len(v["reason"]) > 40
               for v in limits["limits"].values())
    monkeypatch.setattr(parity_keye.W, "ROOT", tmp_path)
    rc = parity_keye.main(["--workload", CELL, "--rehearse", "--degrade",
                           "none,no_relu,no_qk_norm,half_experts"])
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and said["inside_tolerances"], said["faults"]
    readings = said["readings"]
    none = next(v for k, v in readings.items() if k.endswith(".none"))
    # float32 here: the model IS the reference given its selections, and
    # its selections are the reference's; an arm's are not
    assert none["given_selections"]["worst_row_rel_l2"] < 1e-5
    assert none["selections"]["agree_pct"] == 100.0
    assert none["walk_vs_served_rel_l2"] < 1e-5
    for arm in ("no_relu", "no_qk_norm", "half_experts"):
        low = next(v for k, v in readings.items() if k.endswith("." + arm))
        assert low["faults"] and low["selections"]["agree_pct"] < 100.0
        assert low["given_selections"]["worst_row_rel_l2"] > 1e-2


@pytest.mark.parametrize("arm", ["kv_fp8", "index_fp8", "no_relu",
                                 "no_qk_norm", "top1024", "half_experts"])
def test_the_parity_tools_arms_change_what_the_program_computes(params, ids,
                                                               arm):
    """Each arm, built around the served functions while they are traced,
    moves the logits or the selection; outside the context the served
    functions are back."""
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_keye

    def run(cfg):
        return K.prefill_chunk(cfg, params, K.empty_cache(cfg, 32),
                               ids[:32], 0, 32, keep_masks=True)

    def served():
        return (gqa_ops.index_scores, gqa_ops.masked_chunk_gqa,
                ops.index_step, expert_share.held_part_by_shape, K._attn_in)

    logits, _, _, _, masks = run(CFG)
    cfg = parity_keye.cfg_of(CFG, arm)
    assert (cfg.topk == 6) == (arm == "top1024")
    kept = served()
    with parity_keye.lowered(arm):
        low, _, _, _, low_masks = run(cfg)
    assert served() == kept
    # rounding 32 index keys to fp8 need not move a row's 12th place
    assert arm == "index_fp8" \
        or not np.array_equal(np.asarray(logits), np.asarray(low))
    reselected = any(not np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(masks, low_masks))
    if arm in ("no_relu", "top1024"):
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
