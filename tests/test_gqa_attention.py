"""``ops/gqa_attention.py`` and the grouped-query kernel of
``ops/flash_latent.py`` (groups of query heads over a key/value head each,
whole or a band; ONE group is the shared-K/V case), in the Pallas
interpreter and as the ``lax`` statement, against a naive float64 masked
softmax: over starts, band edges inside, at and across blocks,
``window=None``, a ring that is still empty, 6 heads a group, and tiles
whose sides differ (each kernel is served with a pair of its own); since
PR 63 the step by PARTS of a query tile and the grid's traced K extent, at
every chunk position of a small prefill under all three names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import flash_latent, gqa_attention


def case(key, C, S, H, G, d):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (C, H, d)),
            jax.random.normal(kk, (G, S, d)),
            jax.random.normal(kv, (G, S, d)))


def naive(q, k, v, start, scale, window=None, lowest=0):
    C, H, d = q.shape
    G, S, _ = k.shape
    heads = np.repeat(np.arange(G), H // G)       # head h reads h // (H/G)
    s = np.einsum("chd,hsd->chs", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)[heads]) * scale
    row = (start + np.arange(C))[:, None]
    col = np.arange(S)[None, :]
    seen = (col <= row) & (col >= lowest)
    if window is not None:
        seen &= row - col < window
    s = np.where(seen[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("chs,hsd->chd", p / p.sum(-1, keepdims=True),
                     np.asarray(v, np.float64)[heads])


def close(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# (block_q, block_k) of the blocked kernel: square; a K block over two and
# four q blocks (at start 16 the diagonal runs INSIDE a K block they share);
# a q block over four K blocks; a K block LONGER than the chunk
TILES = [(8, 8), (8, 16), (4, 16), (16, 4), (8, 32)]
KERNEL_TILES = [("lax", 8, 8)] + [("interpret", bq, bk) for bq, bk in TILES]


@pytest.mark.parametrize("kernel,block_q,block_k", KERNEL_TILES)
@pytest.mark.parametrize("start", [0, 16, 40])
def test_the_full_kernel_is_naive_attention_with_six_heads_a_group(
        kernel, block_q, block_k, start):
    """12 heads of 16 over 2 key/value heads, a chunk of 16 at three starts
    over 56 rows (padded to the K block inside): the group's tile, the
    moving diagonal, the clamped last block."""
    q, k, v = case(jax.random.key(1), 16, 56, 12, 2, 16)
    got = gqa_attention.causal_chunk(q, k, v, jnp.int32(start), 0.25,
                                     jnp.float32, block_q, block_k,
                                     kernel=kernel)
    assert got.shape == (16, 12, 16)
    assert close(got, naive(q, k, v, start, 0.25))


@pytest.mark.parametrize("kernel,block_q,block_k", KERNEL_TILES)
def test_one_shared_head_is_a_case_of_the_grouped_kernel(kernel, block_q,
                                                         block_k):
    """20 heads over 1 in miniature — 6 heads of 16 over ONE key/value
    head, the chunk at start 16 of 56 rows: the kernel that runs under the
    name ``shared_kv_causal_mha`` is the grouped one with a single group."""
    q, k, v = case(jax.random.key(7), 16, 56, 6, 1, 16)
    got = gqa_attention.causal_chunk(q, k, v, jnp.int32(16), 0.25,
                                     jnp.float32, block_q, block_k,
                                     kernel=kernel)
    assert got.shape == (16, 6, 16)
    assert close(got, naive(q, k, v, 16, 0.25))


# (window, block_q, block_k): the band's lower edge inside a block, at a
# block's first column, across several blocks, a band of ONE key, and
# under tiles far from square: the band's edge and the diagonal in ONE K
# block of several q blocks, a q block over many K blocks, a K block
# longer than the chunk
BANDS = [(5, 8, 8), (8, 8, 8), (16, 8, 8), (19, 4, 8), (12, 8, 4), (1, 8, 8),
         (8, 4, 16), (16, 16, 4), (12, 8, 32), (5, 2, 16)]


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("window,block_q,block_k", BANDS)
@pytest.mark.parametrize("start", [0, 8, 24])
def test_the_band_is_the_last_window_keys(kernel, window, block_q, block_k,
                                          start):
    q, k, v = case(jax.random.key(2), 16, 40, 6, 1, 8)
    got = gqa_attention.causal_chunk(
        q, k, v, jnp.int32(start), 1.0, jnp.float32, block_q, block_k,
        window=window, kernel=kernel)
    assert close(got, naive(q, k, v, start, 1.0, window))


@pytest.mark.parametrize("kernel,block_q,block_k",
                         KERNEL_TILES[:2] + [("interpret", 4, 16),
                                             ("interpret", 16, 32)])
@pytest.mark.parametrize("lowest", [0, 3, 8, 16])
def test_rows_below_the_lowest_valid_one_hold_nothing(kernel, block_q,
                                                      block_k, lowest):
    """``[ring ; chunk]`` with the ring still (partly) empty: queries at
    rows 16.. of 32, a window of 16, the rows below ``lowest`` poisoned
    (a masked logit is replaced, never multiplied) — under the larger
    tiles ``lowest`` falls inside the one K block every q block reads."""
    q, k, v = case(jax.random.key(3), 16, 32, 4, 2, 8)
    bad_k, bad_v = k.at[:, :lowest].set(jnp.nan), v.at[:, :lowest].set(1e30)
    got = gqa_attention.causal_chunk(
        q, bad_k, bad_v, jnp.int32(16), 1.0, jnp.float32, block_q, block_k,
        window=16, lowest=jnp.int32(lowest), kernel=kernel)
    assert np.isfinite(np.asarray(got)).all()
    assert close(got, naive(q, k, v, 16, 1.0, 16, lowest))


def test_blocks_outside_the_band_are_never_read():
    """A band of 8 over 48 rows from row 32: blocks 0–2 lie wholly below
    every query's band and blocks past the diagonal above it."""
    q, k, v = case(jax.random.key(4), 8, 48, 4, 2, 8)
    bad_k = k.at[:, :24].set(jnp.nan).at[:, 40:].set(jnp.nan)
    bad_v = v.at[:, :24].set(jnp.nan).at[:, 40:].set(jnp.nan)
    got = gqa_attention.causal_chunk(
        q, bad_k, bad_v, jnp.int32(32), 1.0, jnp.float32, 8, 8, window=8,
        kernel="interpret")
    assert np.isfinite(np.asarray(got)).all()
    assert close(got, naive(q, k, v, 32, 1.0, 8))


def test_a_model_serves_each_kernel_with_a_pair_of_its_own():
    """The full layer's pair and the band's differ in ONE model, at the
    served size and at the tests'; ``empty_cache`` rounds the full buffer
    to the FULL layer's K block and leaves the rings at the window."""
    from comfyui_distributed_tpu.models import llm_trinity as M

    for cfg, max_len in ((M.TrinityConfig.trinity_share(), 131072 + 128),
                         (M.TrinityConfig.tiny(), 21 + 6)):
        full = (cfg.attn_full_block_q, cfg.attn_full_block_k)
        band = (cfg.attn_window_block_q, cfg.attn_window_block_k)
        assert full != band
        assert cfg.prefill_chunk_tokens % full[0] == 0
        assert cfg.prefill_chunk_tokens % band[0] == 0
        assert 2 * cfg.sliding_window % band[1] == 0  # [ring ; chunk] whole
        cache = jax.eval_shape(lambda: M.empty_cache(cfg, max_len))
        for i, (k, v) in enumerate(zip(cache["k"], cache["v"])):
            rows = -(-max_len // full[1]) * full[1] if cfg.is_full(i) \
                else cfg.sliding_window
            assert k.shape == v.shape == (cfg.num_key_value_heads, rows,
                                          cfg.head_dim)
    tiny = M.TrinityConfig.tiny()
    assert tiny.attn_full_block_k > tiny.prefill_chunk_tokens


def _call_by_name(name, q, k, v, start):
    """The jitted ``name`` of ``flash_latent`` on ``q`` [C,H·d] and ``k``,
    ``v`` [G,S,d] (G = 1 for the shared name, which takes the cache's own
    [S,d]); the window name under a band wider than every row."""
    fn = getattr(flash_latent, name)
    blocks = dict(num_heads=4, block_q=8, block_k=8, interpret=True)
    if name == "gqa_window_mha":
        return fn, (q, k, v, start, jnp.int32(0)), dict(blocks, window=64)
    if name == "shared_kv_causal_mha":
        return fn, (q, k[0], v[0], start), blocks
    return fn, (q, k, v, start), blocks


@pytest.mark.parametrize("name", ["gqa_causal_mha", "gqa_window_mha",
                                  "shared_kv_causal_mha"])
def test_each_name_is_its_own_in_a_program_and_all_are_one_body(
        name, monkeypatch):
    """What a device trace reads (``cdtbench/kinds/jamba.py``,
    ``kinds/trinity.py`` match ``^<name>``) is the jitted function's name in
    the lowered program; what runs under it is ``_gqa_mha``, whatever the
    name: the same answer bit for bit, and no other body is traced."""
    q, k, v = case(jax.random.key(5), 8, 16, 4, 1, 8)
    flat, start = q.reshape(8, 32), jnp.int32(8)
    fn, args, kw = _call_by_name(name, flat, k, v, start)
    assert fn.__name__ == name
    assert f"jit_{name}" in fn.lower(*args, **kw).as_text()
    ref, ref_args, ref_kw = _call_by_name("gqa_causal_mha", flat, k, v, start)
    assert np.array_equal(np.asarray(fn(*args, **kw)),
                          np.asarray(ref(*ref_args, **ref_kw)))
    traced = []
    body = flash_latent._gqa_mha
    monkeypatch.setattr(flash_latent, "_gqa_mha",
                        lambda *a: traced.append(a[5:7]) or body(*a))
    # shapes no other test hands this name: a trace, not a cache hit
    q, k, v = case(jax.random.key(5), 24, 24, 4, 1, 8)
    fn, args, kw = _call_by_name(name, q.reshape(24, 32), k, v, start)
    fn(*args, **kw)
    assert traced == [(4, 64 if name == "gqa_window_mha" else None)]


def _chunk_rows(k, v, position, C, window):
    """What a prefill hands the kernel at chunk ``position`` of ``k``, ``v``
    [G, T, d] (every token's row): a full layer the buffer itself, rows at
    their positions; a window layer ``[ring ; chunk]`` — the ``window`` rows
    ahead of the chunk (zeros where the ring is still empty) and its own —
    with ``start`` the first query's row among them and ``lowest`` the
    first that holds a key."""
    if window is None:
        return k, v, position * C, 0
    at = position * C
    lowest = max(window - at, 0)
    rows = [jnp.pad(a[:, max(at - window, 0):at + C],
                    ((0, 0), (lowest, 0), (0, 0))) for a in (k, v)]
    return *rows, window, lowest


# (name, window, G): the three jitted names of the one body
NAMES = [("gqa_causal_mha", None, 2), ("gqa_window_mha", 16, 2),
         ("shared_kv_causal_mha", None, 1)]
# (block_q, block_k, part): a part below the query tile, the query tile
# itself (the plain step), a query tile of eight parts over a K tile longer
# than the chunk, parts of ONE row
PARTED = [(8, 16, 4), (8, 16, 8), (16, 32, 2), (4, 8, 1)]


@pytest.mark.parametrize("block_q,block_k,part", PARTED)
@pytest.mark.parametrize("name,window,G", NAMES)
def test_the_parted_step_is_the_reference_at_every_chunk_position(
        name, window, G, block_q, block_k, part):
    """A prefill of 72 tokens in chunks of 16 (the LAST one padded: 8 of
    its rows hold no token of the brief) through a buffer of 96 rows /
    ``[ring ; chunk]`` of 16 + 16: at every position the kernel by
    ``part`` rows a product over its traced extent answers what the naive
    softmax answers for the chunk's rows."""
    C, T, H, d = 16, 80, 4, 8
    q, k, v = case(jax.random.key(11), T, 96, H, G, d)
    want = naive(q, k, v, 0, 0.5, window)
    for position in range(T // C):
        kk, vv, start, lowest = _chunk_rows(k, v, position, C, window)
        S = kk.shape[1]
        steps = flash_latent.gqa_k_steps(start, lowest, C, window, block_q,
                                         block_k, S // block_k)
        got = flash_latent.gqa_call(
            (q[position * C:(position + 1) * C] * 0.5).reshape(C, H * d),
            kk, vv, start, lowest, steps, H, window, block_q, block_k, part,
            True)
        assert close(got.reshape(C, H, d),
                     want[position * C:(position + 1) * C]), position


@pytest.mark.parametrize("start", [16, 24, 32])
@pytest.mark.parametrize("lowest", [0, 9, 14])
def test_a_bands_edge_and_the_diagonal_cross_every_part_elsewhere(start,
                                                                  lowest):
    """A query tile of 16 rows in parts of 4 under a band of 6 over K tiles
    of 16: the band's lower edge and the diagonal cross each PART's logits
    at columns of its own (a part's mask starts at ITS first row), in one K
    tile or in two — and rows below ``lowest`` are poisoned (a masked logit
    is replaced, not multiplied)."""
    q, k, v = case(jax.random.key(12), 16, 48, 4, 2, 8)
    bad_k = k.at[:, :lowest].set(jnp.nan)
    steps = flash_latent.gqa_k_steps(start, lowest, 16, 6, 16, 16, 3)
    got = flash_latent.gqa_call(
        q.reshape(16, 32), bad_k, v, start, lowest, steps, 4, 6, 16, 16, 4,
        True)
    assert np.isfinite(np.asarray(got)).all()
    assert close(got.reshape(16, 4, 8),
                 naive(q, k, v, start, 1.0, 6, lowest))


def test_the_rule_for_the_part_reads_the_tile_alone():
    """Parts of 128 rows of every served query tile (ZAYA's and Trinity's
    full layers, Jamba's; the band's), the plain step where 128 does not
    divide the tile (the tiny presets)."""
    from comfyui_distributed_tpu.models.llm_jamba import JambaConfig
    from comfyui_distributed_tpu.models.llm_trinity import TrinityConfig
    from comfyui_distributed_tpu.models.llm_zaya import ZayaConfig

    t = TrinityConfig.trinity_share()
    for block_q in (ZayaConfig.zaya_share().attn_block_q, t.attn_full_block_q,
                    JambaConfig.jamba2_3b().attn_block_q,
                    t.attn_window_block_q):
        assert flash_latent.step_rows(block_q) == flash_latent.STEP_ROWS == 128
    for tiny in (4, 8, 16, 64, 2048 + 64):
        assert flash_latent.step_rows(tiny) == tiny


@pytest.mark.parametrize("C,S,block_q,block_k", [
    (16, 96, 8, 16), (16, 96, 4, 32), (16, 64, 16, 8),
    (4096, 131072, 2048, 2048), (4096, 133120, 2048, 2048)])
def test_the_traced_extent_covers_every_query_tiles_last_block(
        C, S, block_q, block_k):
    """``core_k_steps``, the walk with no band, at every chunk position:
    each query tile's last visible block lies inside the walk, the walk ends
    at the last tile's, and the last chunk of the buffer takes the whole
    grid."""
    nk = S // block_k
    for start in range(0, S - C + 1, C):
        steps = int(flash_latent.gqa_k_steps(start, 0, C, None, block_q,
                                             block_k, nk))
        lasts = [int(flash_latent._last_block(start, i, block_q, block_k,
                                              nk)) for i in range(C // block_q)]
        assert steps == max(lasts) + 1 <= nk
    assert int(flash_latent.core_k_steps(S - C, C, block_k, nk)) == nk
    assert int(flash_latent.core_k_steps(0, C, block_k, nk)) \
        == -(-C // block_k)


@pytest.mark.parametrize("C,window,block_q,block_k", [
    (16, 24, 8, 8), (16, 16, 4, 8), (16, 24, 4, 20),
    (4096, 4096, 1024, 1024), (4096, 4096, 2048, 1024)])
def test_a_bands_walk_is_its_widest_query_tiles_span(C, window, block_q,
                                                     block_k):
    """``gqa_k_steps`` over ``[ring ; chunk]``: every query tile's visible
    blocks, counted from ITS first, lie inside the walk; some tile fills it;
    an empty ring (chunk 0) walks the diagonal's blocks only."""
    S = window + C
    nk = S // block_k
    for lowest in (0, window // 2, window):
        steps = int(flash_latent.gqa_k_steps(window, lowest, C, window,
                                             block_q, block_k, nk))
        spans = []
        for i in range(C // block_q):
            first = int(flash_latent._first_column(
                window + i * block_q, window, lowest)) // block_k
            spans.append(int(flash_latent._last_block(
                window, i, block_q, block_k, nk)) - first + 1)
        assert steps == max(spans) <= nk
    if (block_q, block_k) == (1024, 1024):      # the served band: 5 of 8
        assert (steps, nk) == (4, 8)
        assert int(flash_latent.gqa_k_steps(4096, 0, C, window, block_q,
                                            block_k, nk)) == 5


def test_the_decode_step_reads_the_rows_it_is_told_are_valid():
    q, k, v = case(jax.random.key(6), 1, 24, 6, 2, 8)
    valid = jnp.arange(24) <= 17
    got = gqa_attention.step(q[0], k, v, valid, 0.3, jnp.float32)
    assert close(got, naive(q, k, v, 17, 0.3)[0])
    ring = (jnp.arange(24) <= 17) & (jnp.arange(24) > 9)
    got = gqa_attention.step(q[0], k, v, ring, 0.3, jnp.float32)
    assert close(got, naive(q, k, v, 17, 0.3, window=8)[0])


def test_both_kernels_report_a_tier_of_their_own():
    # index_select_attention registers the one causal tier that is not a
    # kernel of flash_latent.py
    from comfyui_distributed_tpu.ops import (attention,  # noqa: F401
                                             index_select_attention,
                                             kernel_choice)

    for tier in ("gqa_window", "gqa_causal"):
        assert tier in kernel_choice.REPORTED_TIERS
        assert tier not in kernel_choice.TIERS
    assert set(attention.CAUSAL_TIER_REASONS) \
        == set(kernel_choice.REPORTED_TIERS) - set(kernel_choice.TIERS)
    attention.reset_selections()
    attention.note_causal("gqa_window", 48, 128, 4096, 8192, jnp.bfloat16,
                          1024, 1024)
    attention.note_causal("gqa_causal", 48, 128, 4096, 133120, jnp.bfloat16,
                          2048, 2048)
    summary = attention.selection_summary()
    assert "gqa_window:1024/1024" in summary
    assert "gqa_causal:2048/2048" in summary
    attention.reset_selections()


def test_a_site_reports_the_rows_its_step_takes_at_a_time(monkeypatch):
    """What ``causal_chunk`` hands ``note_causal`` on a TPU: the tile and
    ``step_rows`` of it — ``2048/2048/128`` in the ``attention:`` line and
    the counter's ``blocks`` label; a tile the rule leaves whole (the tiny
    presets) reads as it always did."""
    from comfyui_distributed_tpu.ops import attention

    noted = []
    monkeypatch.setattr(attention, "note_causal",
                        lambda *a: noted.append(a))
    monkeypatch.setattr(flash_latent, "gqa_causal_mha",
                        lambda q, *a, **kw: q)
    q, k, v = case(jax.random.key(8), 256, 512, 4, 2, 8)
    gqa_attention.causal_chunk(q, k, v, jnp.int32(0), 1.0, jnp.float32, 256,
                               256, kernel="pallas")
    gqa_attention.causal_chunk(q[:16], k, v, jnp.int32(0), 1.0, jnp.float32,
                               8, 256, kernel="pallas")
    assert [a[0] for a in noted] == ["gqa_causal"] * 2
    assert [a[-3:] for a in noted] == [(256, 256, 128), (8, 256, 8)]
    monkeypatch.undo()
    attention.reset_selections()
    for tile in noted:
        attention.note_causal(*tile)
    summary = attention.selection_summary()
    assert "gqa_causal:256/256/128" in summary
    assert "gqa_causal:8/256" in summary and "8/256/" not in summary
    attention.reset_selections()


# --- the same body behind head-major queries, keys wider than values (PR 64) --


def wide_case(key, C, S, H, G, dk, dv):
    q, k, _ = case(key, C, S, H, G, dk)
    return q, k, jax.random.normal(jax.random.fold_in(key, 9), (G, S, dv))


def naive_wide(q, k, v, seen, scale, sink=None):
    """Float64, a head by itself, the sink one more term of the denominator."""
    H, G = q.shape[1], k.shape[0]
    heads = np.repeat(np.arange(G), H // G)
    s = np.einsum("chd,hsd->chs", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)[heads]) * scale
    e = np.where(seen[:, None, :], np.exp(s), 0.0)
    total = e.sum(-1, keepdims=True)
    if sink is not None:
        total = total + np.exp(np.asarray(sink, np.float64))[None, :, None]
    return np.einsum("chs,hsd->chd", e / total,
                     np.asarray(v, np.float64)[heads])


@pytest.mark.parametrize("kernel,block_q,block_k", KERNEL_TILES)
@pytest.mark.parametrize("start", [0, 16, 40])
def test_the_wide_call_is_naive_attention_at_two_widths(kernel, block_q,
                                                        block_k, start):
    """``gqa_sink_attention.causal_chunk``: 12 heads over 2 key/value heads,
    keys 24 wide and values 16, at ``start`` > 0 — ``_gqa_kernel`` behind a
    head-major query tile whose last dimension is the whole key width."""
    from comfyui_distributed_tpu.ops import gqa_sink_attention

    q, k, v = wide_case(jax.random.key(64), 16, 56, 12, 2, 24, 16)
    scale = 24 ** -0.5
    got = gqa_sink_attention.causal_chunk(q, k, v, start, scale, jnp.float32,
                                          block_q, block_k, kernel=kernel)
    seen = np.arange(56)[None, :] <= (start + np.arange(16))[:, None]
    assert got.shape == (16, 12, 16)
    assert close(got, naive_wide(q, k, v, seen, scale))


@pytest.mark.parametrize("start", [0, 16, 40])
def test_at_one_width_the_wide_call_is_the_grouped_querys_own(start):
    """Keys as wide as values: the new call and ``gqa_causal_mha`` run one
    body and answer alike, bit for bit in the interpreter."""
    from comfyui_distributed_tpu.ops import gqa_sink_attention

    q, k, v = case(jax.random.key(5), 16, 64, 12, 2, 16)
    args = (start, 0.25, jnp.float32, 8, 16)
    ours = gqa_sink_attention.causal_chunk(q, k, v, *args,
                                           kernel="interpret")
    theirs = gqa_attention.causal_chunk(q, k, v, *args, kernel="interpret")
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))


@pytest.mark.parametrize("lowest", [0, "window", 3])
@pytest.mark.parametrize("rows,window", [(32, 8), (24, 8), (13, 4)])
def test_the_block_local_band_with_a_sink_is_the_naive_band(rows, window,
                                                            lowest):
    """``band_chunk`` over ``[ring ; chunk]`` with ``lowest`` > 0 (a ring
    still empty — ``lowest`` the window: every query has its own key at
    least — or partly), a chunk that is no multiple of the window, both
    widths and the sink — and without the sink it is ``gqa_attention``'s band
    at the same rows."""
    from comfyui_distributed_tpu.ops import gqa_sink_attention

    q, k, v = wide_case(jax.random.key(7), rows, window + rows, 12, 4, 24, 16)
    sink = jax.random.normal(jax.random.key(8), (12,))
    scale = 24 ** -0.5
    lowest = window if lowest == "window" else lowest
    row = (window + np.arange(rows))[:, None]
    col = np.arange(window + rows)[None, :]
    seen = (col <= row) & (row - col < window) & (col >= lowest)
    got = gqa_sink_attention.band_chunk(q, k, v, lowest, window, scale,
                                        jnp.float32, sink)
    assert close(got, naive_wide(q, k, v, seen, scale, sink))
    bare = gqa_sink_attention.band_chunk(q, k, v[..., :16], lowest, window,
                                         scale, jnp.float32)
    same = gqa_attention.causal_chunk(
        q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 8))), window, scale,
        jnp.float32, 8, 8, window=window, lowest=lowest, kernel="lax")
    assert close(bare, same[..., :16])
    assert not close(bare, got, 1e-3)


def test_the_wide_step_masks_the_rows_that_hold_no_key_and_joins_the_sink():
    from comfyui_distributed_tpu.ops import gqa_sink_attention

    q, k, v = wide_case(jax.random.key(11), 1, 9, 12, 4, 24, 16)
    sink = jax.random.normal(jax.random.key(12), (12,))
    valid = np.arange(9) <= 5
    for b in (None, sink):
        got = gqa_sink_attention.step(q[0], k, v, jnp.asarray(valid), 0.2,
                                      jnp.float32, b)
        assert close(got, naive_wide(q, k, v, valid[None], 0.2, b)[0])
