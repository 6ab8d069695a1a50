"""Attention over a selection of key blocks
(``ops/block_select_attention.py``): the compressed-key cache as chunks
and tokens write it, the block scores and tables against a brute-force
``argsort``, the forced blocks, the causal cut inside the own block, the
chunk form against the step form through the cache, and the Pallas kernel
(in the interpreter here) against the masked softmax — also for a table
whose blocks are not contiguous."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import block_select_attention as bsa

SEL = bsa.Selection(kernel_size=4, kernel_stride=2, block_size=8,
                    init_blocks=1, window_size=16, topk=3)
G, J, D = 2, 3, 8
H = G * J
SCALE = D ** -0.5
# float32 sums of a few hundred terms: 1e-6; an unmasked row, a wrong
# block or a missing causal cut moves an output by 1e-1
TOL = 5e-6


def rows(S, Q, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (G, S, D)),
            jax.random.normal(ks[1], (G, S, D)),
            jax.random.normal(ks[2], (Q, H, D)))


def sparse_chunk(*args, **kw):
    """The chunk form's answers (its step counts have tests of their own)."""
    return bsa.sparse_chunk(*args, **kw)[0]


def compressed(k, upto, chunk=16):
    """The compressed cache after chunks of ``chunk`` rows wrote ``k`` up
    to row ``upto``."""
    S = k.shape[1]
    kc, cache = jnp.zeros((G, S // 2, D)), jnp.zeros_like(k)
    for st in range(0, upto, chunk):
        n = min(chunk, upto - st)
        cache = cache.at[:, st:st + chunk].set(k[:, st:st + chunk])
        kc = bsa.compress_chunk(kc, cache, k[:, st:st + chunk], st, n, SEL)
    return kc, cache


def brute_tables(q, k, pos):
    """Tables by the rule, a query and a group at a time, in numpy."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    S, nb = k.shape[1], k.shape[1] // SEL.block_size
    out = np.zeros((G, len(pos), nb), bool)
    for g in range(G):
        for n, t in enumerate(pos):
            starts = [j * 2 for j in range((S - 4) // 2 + 1)
                      if j * 2 + 4 <= t + 1]
            score = np.zeros(nb)
            if starts:
                means = np.stack([k[g, s:s + 4].mean(0) for s in starts])
                logits = q[n, g * J:(g + 1) * J] @ means.T * SCALE
                p = np.exp(logits - logits.max(-1, keepdims=True))
                p = (p / p.sum(-1, keepdims=True)).sum(0)
                for b in range(nb):
                    near = [p[i] for i, s in enumerate(starts)
                            if s < 8 * b + 8 and s + 4 > 8 * b]
                    score[b] = max(near, default=0.0)
            own = t // 8
            for b in range(nb):
                if b > own:
                    score[b] = -np.inf
                elif b == 0 or b > own - 2:
                    score[b] = np.inf
            order = np.argsort(-score, kind="stable")[:SEL.table]
            out[g, n, [b for b in order if score[b] > -np.inf]] = True
    return out


def test_the_rules_sizes():
    assert (SEL.per, SEL.local_blocks, SEL.table) == (4, 2, 5)
    full = bsa.Selection()
    assert (full.per, full.local_blocks, full.table) == (4, 32, 96)
    with pytest.raises(ValueError):
        bsa.Selection(kernel_size=3, kernel_stride=2).check()


def test_chunks_write_the_means_of_their_windows_across_a_chunk_edge():
    k, _, _ = rows(96, 1)
    kc, _ = compressed(k, 96)
    for j in range((96 - 4) // 2 + 1):           # window j sits at slot j + 1
        assert np.allclose(kc[:, j + 1], k[:, 2 * j:2 * j + 4].mean(1),
                           atol=1e-6), j
    assert not np.asarray(kc[:, 0]).any()        # slot 0 holds no window


@pytest.mark.parametrize("n_valid", [1, 2, 3, 9, 16])
def test_a_padded_chunk_writes_only_the_slots_its_real_rows_complete(n_valid):
    k, _, _ = rows(96, 1)
    kc, cache = compressed(k, 32)
    before = np.asarray(kc).copy()
    cache = cache.at[:, 32:48].set(k[:, 32:48])
    after = np.asarray(bsa.compress_chunk(kc, cache, k[:, 32:48], 32,
                                          jnp.asarray(n_valid), SEL))
    done = (32 + n_valid) // 2 - 1               # windows whole by then
    assert np.allclose(after[:, 1:done + 1], [
        [np.asarray(k[g, 2 * j:2 * j + 4]).mean(0) for j in range(done)]
        for g in range(G)], atol=1e-6)
    assert (after[:, done + 1:] == before[:, done + 1:]).all()


def test_tokens_write_a_slot_every_stride_rows_as_the_chunks_did():
    k, _, _ = rows(96, 1)
    kc, cache = compressed(k, 32)
    want, _ = compressed(k, 48)
    for pos in range(32, 48):
        cache = cache.at[:, pos].set(k[:, pos])
        grown = bsa.compress_step(kc, cache, pos, SEL)
        changed = int((np.asarray(grown) != np.asarray(kc)).any((0, 2)).sum())
        assert changed == (1 if (pos + 1) % 2 == 0 else 0)
        kc = grown
    assert np.allclose(kc, want, atol=1e-6)


@pytest.mark.parametrize("start", [0, 8, 40, 64])
def test_the_tables_are_the_rules_by_a_stable_argsort(start):
    k, _, q = rows(96, 32, seed=start + 2)
    kc, _ = compressed(k, start + 32)
    pos = start + jnp.arange(32)
    score = bsa.block_scores(q, kc, pos, SCALE, jnp.float32, SEL)
    chosen = np.asarray(bsa.select(score, SEL))
    assert (chosen == brute_tables(q, k, np.asarray(pos))).all()
    own = np.asarray(pos) // 8
    assert (chosen.sum(-1) == np.minimum(own + 1, SEL.table)).all()
    for n, b in enumerate(own):                  # the forced blocks
        assert chosen[:, n, 0].all() and chosen[:, n, b].all()
        assert chosen[:, n, max(b - 1, 0)].all()
        assert not chosen[:, n, b + 1:].any()


def test_neighbouring_blocks_tie_and_the_lower_index_wins():
    score = jnp.asarray([[[jnp.inf, 0.5, 0.7, 0.7, 0.7, 0.2, jnp.inf,
                           -jnp.inf]]])
    sel = SEL._replace(topk=2, window_size=8)      # a table of 3
    assert np.asarray(bsa.select(score, sel))[0, 0].tolist() == [
        True, False, True, False, False, False, True, False]


def test_a_tiles_union_is_ascending_and_repeats_its_last_entry():
    chosen = np.zeros((1, 4, 7), bool)
    chosen[0, 0, [0, 2]] = chosen[0, 1, [0, 5]] = True
    chosen[0, 2, [0, 1]] = chosen[0, 3, [0, 1, 6]] = True
    union, count, mask, _ = bsa.tile_unions(jnp.asarray(chosen), 2, 4)
    assert count.tolist() == [[3, 3]]
    assert union[0, 0].tolist() == [0, 2, 5, 5, 5, 5, 5, 5]
    assert union[0, 1].tolist() == [0, 1, 6, 6, 6, 6, 6, 6]
    assert mask.shape == (1, 2, 2, 2, 4)
    # tile 0, step 0: query 0 owns entries 0 and 1 (blocks 0, 2), query 1
    # entries 0 and 2 (blocks 0, 5); nothing past the count
    assert mask[0, 0, 0].tolist() == [[1, 1, 0, 0], [1, 0, 1, 0]]
    assert not np.asarray(mask[0, :, 1]).any()


@pytest.mark.parametrize("kernel,block_q,per_step", [
    ("lax", 8, 2), ("interpret", 8, 2), ("interpret", 16, 4),
    ("interpret", 4, 1)])
def test_the_chunk_form_is_the_masked_softmax_over_the_selected_rows(
        kernel, block_q, per_step):
    k, v, q = rows(96, 32)
    kc, cache = compressed(k, 80)
    chosen = bsa.select(bsa.block_scores(q, kc, 48 + jnp.arange(32), SCALE,
                                         jnp.float32, SEL), SEL)
    got = sparse_chunk(q, cache, v, chosen, 48, SCALE, jnp.float32, SEL,
                           block_q, per_step, kernel)
    s = np.einsum("qgjd,gsd->qgjs",
                  np.asarray(q, np.float64).reshape(32, G, J, D),
                  np.asarray(cache, np.float64)) * SCALE
    seen = np.repeat(np.asarray(chosen), 8, -1) \
        & (np.arange(96)[None, None] <= (48 + np.arange(32))[None, :, None])
    s = np.where(np.swapaxes(seen, 0, 1)[:, :, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("qgjs,gsd->qgjd", p / p.sum(-1, keepdims=True),
                     np.asarray(v, np.float64)).reshape(32, H, D)
    assert np.abs(np.asarray(got) - want).max() < TOL


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_a_table_whose_blocks_are_not_contiguous(kernel):
    """Hand-made tables with gaps, different for every query and group:
    only their rows are read."""
    k, v, q = rows(96, 16, seed=7)
    rng = np.random.default_rng(0)
    chosen = np.zeros((G, 16, 12), bool)
    for g in range(G):
        for n in range(16):
            own = (64 + n) // 8
            chosen[g, n, rng.choice(own, 3, replace=False)] = True
            chosen[g, n, own] = True
    got = sparse_chunk(q, k, v, jnp.asarray(chosen), 64, SCALE,
                           jnp.float32, SEL, 8, 2, kernel)
    # rows outside the tables may hold anything
    outside = ~np.repeat(chosen.any(1), 8, -1)               # [G, S]
    loud_k = jnp.where(outside[:, :, None], 1e4, k)
    loud_v = jnp.where(outside[:, :, None], -1e4, v)
    again = sparse_chunk(q, loud_k, loud_v, jnp.asarray(chosen), 64,
                             SCALE, jnp.float32, SEL, 8, 2, kernel)
    assert np.abs(np.asarray(got) - np.asarray(again)).max() < TOL
    lax = sparse_chunk(q, k, v, jnp.asarray(chosen), 64, SCALE,
                           jnp.float32, SEL, kernel="lax")
    assert np.abs(np.asarray(got) - np.asarray(lax)).max() < TOL


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_the_causal_cut_inside_the_own_block(kernel):
    """Rows of a query's own block past its position are never read."""
    k, v, q = rows(96, 8, seed=3)
    kc, cache = compressed(k, 48)
    pos = 40 + jnp.arange(8)                      # the whole of block 5
    chosen = bsa.select(bsa.block_scores(q, kc, pos, SCALE, jnp.float32,
                                         SEL), SEL)
    got = sparse_chunk(q, cache, v, chosen, 40, SCALE, jnp.float32, SEL,
                           8, 2, kernel)
    for n in (0, 3, 6):
        loud_k = cache.at[:, 40 + n + 1:48].set(1e4)
        loud_v = v.at[:, 40 + n + 1:48].set(-1e4)
        again = sparse_chunk(q, loud_k, loud_v, chosen, 40, SCALE,
                                 jnp.float32, SEL, 8, 2, kernel)
        assert np.abs(np.asarray(got[n]) - np.asarray(again[n])).max() < TOL
        assert np.abs(np.asarray(got[7]) - np.asarray(again[7])).max() > 1.0


@pytest.mark.parametrize("start", [0, 48])
def test_the_chunk_form_is_the_step_form_through_the_cache(start):
    k, v, q = rows(96, 32, seed=5)
    kc, cache = compressed(k, start + 32)
    chosen = bsa.select(bsa.block_scores(q, kc, start + jnp.arange(32),
                                         SCALE, jnp.float32, SEL), SEL)
    whole = sparse_chunk(q, cache, v, chosen, start, SCALE, jnp.float32,
                             SEL, 8, 2, "interpret")
    kc_t, cache_t = compressed(k, start) if start else (
        jnp.zeros((G, 48, D)), jnp.zeros_like(k))
    for n in range(32):
        pos = start + n
        cache_t = cache_t.at[:, pos].set(k[:, pos])
        kc_t = bsa.compress_step(kc_t, cache_t, pos, SEL)
        o, table = bsa.sparse_step(q[n], cache_t, v, kc_t, pos, SCALE,
                                   jnp.float32, SEL)
        assert np.abs(np.asarray(o) - np.asarray(whole[n])).max() < TOL, n
        assert (np.asarray(table) == np.asarray(chosen[:, n])).all(), n


def test_the_kernel_reports_a_tier_of_its_own(monkeypatch):
    from comfyui_distributed_tpu.ops import attention, kernel_choice

    assert "block_select" in kernel_choice.REPORTED_TIERS
    assert "block_select" in attention.CAUSAL_TIER_REASONS
    said = []
    monkeypatch.setattr(attention, "_note_selection",
                        lambda geometry, choice, blocks: said.append(
                            (geometry, choice.tier, choice.block_q,
                             choice.block_k, blocks)))
    attention.note_causal("block_select", 32, 128, 512, 65664,
                          jnp.bfloat16, 64, 1024)
    assert said == [("h32.d128.q512.kv131072.bf16", "block_select", 64,
                     1024, "64/1024")]


# --- a step's two fetches ---------------------------------------------------------

_spec = importlib.util.spec_from_file_location(
    "sparse_step_sweep",
    Path(__file__).resolve().parent.parent / "scripts" / "sparse_step_sweep.py")
sweep = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep)


def _table(start, Q, nb, blocks, thin=True):
    """``chosen`` [G, Q, nb]: every query at ``start …`` reads the blocks
    ``blocks(own)`` gives for its own block — less, where ``thin``, a third
    of them (which third turns with the query and the group, so the mask
    is every query's own and the union still the whole list), never block
    0 nor its own."""
    chosen = np.zeros((G, Q, nb), bool)
    for g in range(G):
        for n in range(Q):
            own = (start + n) // 8
            for b in blocks(own):
                if not thin or b in (0, own) or (b + n + g) % 3:
                    chosen[g, n, b] = True
    return chosen


# (start, queries, blocks, the table's blocks by a query's own block,
#  queries a tile, blocks a step) -> grid steps (run, blocks, skipped)
STEP_CASES = {
    "every step a run, the own block inside the last": (
        (56, 8, 12, lambda own: range(own + 1), 8, 2), (8, 0, 4)),
    "no step a run": (
        (64, 8, 12, lambda own: range(own % 2, own + 1, 2), 8, 2),
        (0, 6, 6)),
    "a run ends at the tile's count inside a step": (
        (48, 8, 12, lambda own: range(own + 1), 8, 4), (2, 2, 2)),
    "a count below one step": (
        (8, 8, 12, lambda own: range(own + 1), 8, 4), (0, 2, 4)),
    "two queries a block: the cut in both of a tile's last steps": (
        (40, 16, 12, lambda own: range(own + 1), 4, 2), (24, 4, 20)),
    "three tiles, runs then scattered blocks": (
        (64, 24, 16, lambda own: [*range(4), *range(5, own - 1, 2), own],
         8, 2), (12, 10, 26)),
    "16 blocks a step": (
        (248, 16, 48, lambda own: range(own + 1), 8, 16), (8, 2, 2)),
}


@pytest.mark.parametrize("case,steps", STEP_CASES.values(),
                         ids=STEP_CASES.keys())
def test_a_step_fetches_by_its_table_and_answers_as_the_parent_did(case,
                                                                   steps):
    """The kernel (in the interpreter) over hand-made tables: the masked
    softmax, PR 49's kernel TO THE BIT (the same mask, products and
    reductions in the same order: only how the rows arrive differs), and
    its steps by fetch counted from the table."""
    start, Q, nb, blocks, block_q, per_step = case
    k, v, q = rows(8 * nb, Q, seed=17)
    chosen = jnp.asarray(_table(start, Q, nb, blocks))
    got, fetches = bsa.sparse_chunk(q, k, v, chosen, start, SCALE,
                                    jnp.float32, SEL, block_q, per_step,
                                    "interpret")
    lax = sparse_chunk(q, k, v, chosen, start, SCALE, jnp.float32, SEL,
                       kernel="lax")
    assert np.abs(np.asarray(got) - np.asarray(lax)).max() < TOL
    assert tuple(fetches.tolist()) == steps
    union, count, mask, _ = bsa.tile_unions(chosen, block_q, per_step)
    assert sum(steps) == union.size // per_step
    tiles = bsa._head_major_tiles(q * SCALE, G, block_q)
    parent = sweep.parent_mha(tiles, k, v, union, count, mask, start,
                              block=8, interpret=True)
    mine = bsa.block_select_mha(tiles, k, v, union, count, mask, start,
                                block=8, interpret=True)
    assert (np.asarray(mine) == np.asarray(parent)).all()


@pytest.mark.parametrize("fetch,mask_form", [
    ("specs", "bias"), ("copies", "bias"), ("copies", "bias_selects")])
def test_the_forms_that_lost_the_sweep_keep_the_parents_bits(fetch,
                                                             mask_form):
    """The sweep's other forms of the step — the mask as an ADDED bias over
    PR 49's fetch and over the shipped one — answer as both ends do: what
    the sweep times are the same answers."""
    start, Q, nb, blocks, block_q, per_step = STEP_CASES[
        "three tiles, runs then scattered blocks"][0]
    k, v, q = rows(8 * nb, Q, seed=19)
    chosen = jnp.asarray(_table(start, Q, nb, blocks))
    union, count, mask, _ = bsa.tile_unions(chosen, block_q, per_step)
    tiles = bsa._head_major_tiles(q * SCALE, G, block_q)
    args = (tiles, k, v, union, count, mask, start)
    parent = sweep.parent_mha(*args, block=8, interpret=True)
    half = sweep.step_form_mha(*args, block=8, fetch=fetch,
                               mask_form=mask_form, interpret=True)
    assert (np.asarray(half) == np.asarray(parent)).all()


def test_a_query_with_nothing_in_a_tiles_first_step_is_still_right():
    """A query whose first entry comes in a LATER step of its tile: its
    heads' rows are all ``NEG_INF`` in step 0 (running maximum and all),
    and what they gathered there is scaled away by the first real
    logit."""
    k, v, q = rows(96, 8, seed=23)
    chosen = np.zeros((G, 8, 12), bool)
    chosen[:, :4, [0, 1]] = True                 # step 0 of the union
    chosen[:, 4:, [5, 6]] = True                 # step 1 alone
    chosen = jnp.asarray(chosen)
    got = sparse_chunk(q, k, v, chosen, 56, SCALE, jnp.float32, SEL, 8, 2,
                       "interpret")
    lax = sparse_chunk(q, k, v, chosen, 56, SCALE, jnp.float32, SEL,
                       kernel="lax")
    assert np.abs(np.asarray(got) - np.asarray(lax)).max() < TOL


def test_the_sweeps_tables_are_the_three_kinds():
    """``scripts/sparse_step_sweep.py``'s tables at the test's sizes:
    clustered tiles choose alike (a union is one query's table), scattered
    unions hold no two adjacent blocks (so no step is a run), and every
    table holds the query's own block."""
    sel = bsa.Selection(kernel_size=4, kernel_stride=2, block_size=8,
                        init_blocks=1, window_size=16, topk=6)
    _, _, q = rows(8, 16)
    kc = jnp.zeros((G, 4 * 32, D))
    for kind in ("clustered", "scattered", "lone"):
        chosen = sweep.chosen_of(kind, jax.random.key(3), q, kc, 192, SCALE,
                                 jnp.float32, sel, 8)
        own = (192 + np.arange(16)) // 8
        assert np.asarray(chosen)[:, np.arange(16), own].all(), kind
        union, count, _, fetches = bsa.tile_unions(chosen, 8, 2)
        per_query = np.asarray(chosen).sum(-1)
        if kind == "clustered":
            assert (per_query == sel.table).all()
            assert (np.asarray(count) == sel.table).all()
        elif kind == "scattered":
            assert (per_query == sel.table).all()
            blocks = np.asarray(chosen).reshape(G, 2, 8, -1).any(2)
            assert not (blocks[..., 1:] & blocks[..., :-1]).any()
            assert fetches[0] == 0 and fetches[1] > 2 * G
        else:
            assert (np.asarray(count) == 1).all()
            assert fetches.tolist() == [0, 2 * G, 2 * G * 15]


# --- the two-pass scoring kernel (in the interpreter) ------------------------


def _scored(S, Q, start, seed, block_q, block_slots, kernel):
    """Block scores of ``Q`` neighbours at ``start`` over a cache of ``S``
    rows whose EVERY slot holds something loud (the rule alone decides what
    a query sees, not what a chunk has written)."""
    k, _, q = rows(S, Q, seed=seed)
    kc = 3.0 * k[:, :S // 2]
    return q, bsa.block_scores(q, kc, start + jnp.arange(Q), SCALE,
                               jnp.float32, SEL, kernel, block_q,
                               block_slots)


# (cache rows, queries, start, query tile, slot tile): Sc = rows / 2 slots
SCORE_CASES = {
    "start 0: the first tiles see no whole window": (512, 32, 0, 8, 128),
    "a chunk edge": (512, 32, 256, 8, 128),
    "mid-cache, one query tile": (512, 32, 300, 32, 128),
    # queries 250..281 see slots ..124 … ..139: the tile of 16 at 250 sees
    # slot tile 1 (slots 128 …) only through its later queries
    "a query tile straddles a slot tile's edge": (1024, 32, 250, 16, 128),
    "later slot tiles all skipped": (2048, 16, 40, 8, 128),
    "one slot tile": (512, 32, 96, 8, 256),
    "a query tile that does not divide": (512, 24, 64, 16, 128),
}


@pytest.mark.parametrize("case", SCORE_CASES.values(), ids=SCORE_CASES.keys())
def test_the_scoring_kernel_is_the_plain_softmax_summed_over_a_group(case):
    S, Q, start, block_q, block_slots = case
    q, want = _scored(S, Q, start, 11, block_q, block_slots, "lax")
    _, got = _scored(S, Q, start, 11, block_q, block_slots, "interpret")
    want, got = np.asarray(want), np.asarray(got)
    forced = ~np.isfinite(want)
    assert (got[forced] == want[forced]).all()
    assert np.abs(got[~forced] - want[~forced]).max() < 1e-6
    # tie-free inputs: the TABLES are equal
    assert (np.asarray(bsa.select(jnp.asarray(got), SEL))
            == np.asarray(bsa.select(jnp.asarray(want), SEL))).all()


def _sums_both_ways(S, Q, start, block_q, block_slots, seed=13):
    k, _, q = rows(S, Q, seed=seed)
    kc = 3.0 * k[:, :S // 2]
    bq, slots = bsa.score_tiles(Q, S // 2, block_q, block_slots)
    got = bsa.block_score_sums(
        bsa._head_major_tiles(q * SCALE, G, bq), kc, jnp.int32(start),
        block_q=bq, block_slots=slots, stride=SEL.kernel_stride,
        interpret=True)
    want = bsa._group_sums(q * SCALE, kc, start + jnp.arange(Q),
                           SEL.kernel_stride)
    return np.asarray(got), np.asarray(want)


def test_a_tile_with_no_whole_window_reads_zero():
    """Positions 0 … 2 complete no window of four rows: their rows are 0
    everywhere (never a softmax over nothing), their neighbours' are not."""
    got, want = _sums_both_ways(512, 8, 0, 8, 128)
    assert np.abs(got - want).max() < 1e-6
    assert not got[:, :3].any() and got[:, 3:, 1].all()
    assert np.allclose(got[:, 3:].sum(-1), J, atol=1e-5)


@pytest.mark.parametrize("start,block_q,scored", [
    (40, 8, 1), (250, 16, 2), (250, 32, 2), (600, 8, 3), (2040, 8, 8)])
def test_skipped_slot_tiles_are_written_as_zeros(start, block_q, scored):
    """A slot tile past the last one a query tile sees is never computed —
    and reads exactly 0, never what the buffer held: the sums over a cache
    of 1024 slots in tiles of 128, the skipped tiles counted by the rule
    the counter uses."""
    got, want = _sums_both_ways(2048, 32, start, block_q, 128)
    assert np.abs(got - want).max() < 1e-6
    last = bsa.last_slot_tile(start + np.arange(0, 32, block_q), block_q,
                              128, SEL.kernel_stride, 8, np.clip)
    assert last.max() + 1 == scored
    for i, t in enumerate(last):
        tile = got[:, i * block_q:(i + 1) * block_q]
        assert not tile[:, :, (t + 1) * 128:].any()
        assert tile[:, :, t * 128:(t + 1) * 128].any()


def test_one_token_scores_in_the_plain_form(monkeypatch):
    """``sparse_step`` holds no Pallas call whatever the platform says."""
    monkeypatch.setattr(bsa.flash_attention, "_platform", lambda: "tpu")
    k, v, q = rows(96, 1)
    kc, cache = compressed(k, 64)
    text = jax.jit(lambda: bsa.sparse_step(
        q[0], cache, v, kc, 63, SCALE, jnp.float32, SEL)).lower().as_text()
    assert "pallas" not in text and "custom_call" not in text
