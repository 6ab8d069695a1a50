"""The third prompt rewriter (latent attention with a low-rank query and
YaRN on every layer, a prefill walked in chunks through the latent cache,
routed experts by group) at the tiny float32 preset, against the plain
reference on seeded weights: the chunked prefill at several chunk lengths
and through both forms of the blocked attention, decode through the cache,
the blocked causal kernel at the published widths, YaRN's table, the three
forms of the expert layer and the rule that picks one, the chip's share of
the experts, the shared pipeline, the nodes, the shipped graph and the
benchmark's files and readers of the cell."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_kimi as K
from comfyui_distributed_tpu.models import llm_kimi_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import expert_share, latent_attention

ROOT = Path(__file__).resolve().parent.parent
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = K.KimiConfig.tiny()
CELL = "kimi-k2.6.brief32k-sdxl8"
T = 37                  # spans chunks, blocks and YaRN's original length


@pytest.fixture(scope="module")
def params():
    return K.init_kimi(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (T,), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def full_logits(params, ids):
    return R.forward(CFG, params, ids)


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# --- prefill through the cache, decode through the cache ----------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.moe_layers == [1, 2, 3, 4] and not CFG.is_moe(0)
    assert T > 2 * CFG.prefill_chunk_tokens > CFG.rope_original_len
    assert CFG.router_experts > CFG.n_routed_experts == CFG.num_experts
    assert expert_share.prefill_form(CFG.prefill_chunk_tokens, CFG.routing,
                                     CFG.expert_tile) == "grouped"
    assert CFG.model.prefill_chunk is K.prefill_chunk


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("chunk", [16, 8, 10, T])
def test_chunked_prefill_is_the_reference_at_every_position(
        params, ids, full_logits, chunk, kernel):
    """8 and 16 cut the prompt into chunks with a padded last one, 10
    neither divides T nor is a multiple of the attention blocks, T is the
    whole prompt as one chunk."""
    want, want_held = full_logits
    got, cache, held = K.prefill(CFG, params, ids, T + 3, all_logits=True,
                                 chunk=chunk, kernel=kernel)
    assert got.shape == (T, CFG.vocab_size) and close(got, want)
    assert held.tolist() == [int(h) for h in want_held[1:]]
    assert all(c.shape[0] >= T + 3 for c in cache["c"])
    last = K.prefill(CFG, params, ids, T + 3, chunk=chunk, kernel=kernel)[0]
    assert close(last, want[-1])


def test_a_chunk_continues_from_the_cache_the_chunks_before_it_left(
        params, ids, full_logits):
    """The continuation by hand: three calls of ``prefill_chunk``, the
    cache the only thing between them."""
    cache = K.empty_cache(CFG, 48)
    rows = []
    for start in (0, 16, 32):
        n = min(16, T - start)
        chunk_ids = jnp.pad(ids[start:start + n], (0, 16 - n))
        logits, cache, held, mult = K.prefill_chunk(
            CFG, params, cache, chunk_ids, start, n, all_logits=True)
        rows.append(logits[:n])
        assert held.shape == mult.shape == (4,)
        assert (np.asarray(mult) >= np.asarray(held)).all()
    assert close(jnp.concatenate(rows), full_logits[0])


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        params, ids, full_logits):
    want, want_held = full_logits
    split = 20
    logits, cache, held = K.prefill(CFG, params, ids[:split], T)
    assert close(logits, want[split - 1])
    total = np.asarray(held)
    for t in range(split, T):
        logits, cache, held = K.decode_step(CFG, params, cache, ids[t], t)
        assert close(logits, want[t]), t
        total = total + np.asarray(held)
    assert total.tolist() == [int(h) for h in want_held[1:]]


def test_the_reference_in_query_blocks_is_the_reference(params, ids,
                                                        full_logits):
    blocked, held = R.forward(CFG, params, ids, positions=[T - 1, 3],
                              block=16)
    assert close(blocked, full_logits[0][jnp.asarray([T - 1, 3])], 1e-6)
    assert [int(h) for h in held] == [int(h) for h in full_logits[1]]


def test_a_bfloat16_run_fails_the_float32_tolerance(params, ids, full_logits):
    low = dataclasses.replace(CFG, dtype="bfloat16")
    got = K.prefill(low, params, ids, T, all_logits=True)[0]
    assert not close(got, full_logits[0])
    assert close(got, full_logits[0], 0.2)


# --- attention ----------------------------------------------------------------


def _attention_case(key, T, H, nope, rope, v, rank):
    ks = jax.random.split(key, 5)
    q_nope = jax.random.normal(ks[0], (T, H, nope))
    q_rope = jax.random.normal(ks[1], (T, H, rope))
    c = jax.random.normal(ks[2], (T, rank))
    k_rope = jax.random.normal(ks[3], (T, rope))
    w_b = jax.random.normal(ks[4], (rank, H * (nope + v))) / math.sqrt(rank)
    return q_nope, q_rope, c, k_rope, w_b


# (block_q, block_k): square; a K block under four q blocks (the second
# chunk's diagonal runs INSIDE the one they share); a q block over four K
# blocks; a K block LONGER than the chunk (the workspace pads to lcm(C, K))
@pytest.mark.parametrize("block_q,block_k", [(16, 16), (8, 32), (32, 8),
                                             (16, 64)])
@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_the_blocked_causal_kernel_is_naive_attention_at_kimis_widths(
        kernel, block_q, block_k):
    """192-wide queries and keys (128 + the shared 64-wide rope key),
    128-wide values; two chunks of 32 rows over a cache of 72, so that
    blocks lie below, on and above the diagonal."""
    H, nope, rope, v, rank, C = 2, 128, 64, 128, 32, 32
    q_nope, q_rope, c, k_rope, w_b = _attention_case(
        jax.random.key(3), 2 * C, H, nope, rope, v, rank)
    scale = 0.05
    want = latent_attention.mla_naive(q_nope, q_rope, c, k_rope, w_b, scale,
                                      jnp.float32)
    cache = jnp.zeros((72, rank)).at[:2 * C].set(c)
    kr = jnp.zeros((72, rope)).at[:2 * C].set(k_rope)
    for start in (0, C):
        got = latent_attention.mla_chunk_attention(
            q_nope[start:start + C], q_rope[start:start + C], cache, kr,
            jnp.asarray(start), w_b, scale, jnp.float32, block_q, block_k,
            kernel)
        assert got.shape == (C, H, v)
        assert close(got, want[start:start + C], 1e-5), start


def test_rows_above_the_chunk_are_never_read():
    """What lies in the cache past the chunk's own rows (a longer prompt's
    rows, a padded chunk's) moves nothing."""
    H, nope, rope, v, rank, C = 2, 8, 8, 8, 16, 16
    q_nope, q_rope, c, k_rope, w_b = _attention_case(
        jax.random.key(4), 48, H, nope, rope, v, rank)
    outs = []
    for tail in (0.0, 1e4):
        cache = c.at[32:].set(tail)
        kr = k_rope.at[32:].set(tail)
        outs.append([latent_attention.mla_chunk_attention(
            q_nope[16:32], q_rope[16:32], cache, kr, jnp.asarray(16), w_b,
            0.3, jnp.float32, 8, 8, kernel) for kernel in ("lax",
                                                           "interpret")])
    for a, b in zip(*outs):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# (H, nope, v, rank): the tiny preset's; Kimi's and LongCat's heads at
# their published widths; Ling's 32; a value narrower than the key (the
# Pallas prefill kernel needs nope == v, the decode step never did)
STEP_GEOMETRIES = [(4, 8, 8, 16), (64, 128, 128, 64), (32, 128, 128, 64),
                   (4, 16, 8, 16)]


@pytest.mark.parametrize("form", ["stored", "absorbed"])
@pytest.mark.parametrize("geometry", STEP_GEOMETRIES,
                         ids=lambda g: "h{}.nope{}.v{}.rank{}".format(*g))
def test_the_absorbed_step_is_the_naive_attention_rows_last(geometry, form):
    """``W_b`` as the weight tree stores it, and in the form a token loop
    makes of it once ahead of its steps (``absorbed_form``): the same
    answer, float32 to 1e-5."""
    H, nope, v, rank = geometry
    rope, n = 8, 11
    q_nope, q_rope, c, k_rope, w_b = _attention_case(
        jax.random.key(5), n, H, nope, rope, v, rank)
    want = latent_attention.mla_naive(q_nope, q_rope, c, k_rope, w_b, 0.3,
                                      jnp.float32)[-1]
    assert want.shape == (H, v)
    cache = jnp.zeros((16, rank)).at[:n].set(c)
    kr = jnp.zeros((16, rope)).at[:n].set(k_rope)
    if form == "absorbed":
        w_b = latent_attention.absorbed_form(w_b, H)
        assert w_b.shape == (rank, H, nope + v)
    got = latent_attention.mla_absorbed_step(
        q_nope[-1], q_rope[-1], cache, kr, n - 1, w_b, 0.3, jnp.float32)
    assert close(got, want, 1e-5)


@pytest.mark.parametrize("preset", ["kimi-tiny", "longcat-tiny"])
def test_decode_over_the_form_made_ahead_of_the_loop_is_bit_equal(preset):
    """``llm_decode`` of a model that gives ``decode_weights`` — bfloat16,
    as the cells run it — against the same program reading the stored
    leaves inside the loop (what it was until PR 45): the same ids and the
    same logits, bit for bit, and the form is made once a latent sublayer
    AHEAD of the scan (no ``w_b``-sized reshape inside it)."""
    from comfyui_distributed_tpu.models.registry import PRESETS

    cfg = dataclasses.replace(PRESETS[preset].llm, dtype="bfloat16")
    assert cfg.model.decode_weights is not None
    params = cfg.model.init(cfg, jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (20,), 0, cfg.vocab_size)
    pipe = pipeline_llm.LLMPipeline(cfg, params)
    logits, cache, *_ = pipe.prefill_fn(20, 12)(ids)
    stored = pipeline_llm.LLMPipeline(cfg, params)
    stored.model = cfg.model._replace(decode_weights=None)
    args = (logits, cache, jax.random.key(3), jnp.asarray(0.7, jnp.float32))
    got = pipe.decode_fn(20, 12, tap_every=3)(*args)
    want = stored.decode_fn(20, 12, tap_every=3)(*args)
    assert got[1].shape == (4, cfg.vocab_size)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    text = str(jax.make_jaxpr(pipe.decode_fn(20, 12).jitted)(params, *args))
    ahead, loop = text.split("scan[", 1)
    assert ahead.count("optimization_barrier") == len(cache["c"]) >= 4
    assert "optimization_barrier" not in loop


def test_yarns_table_is_the_closed_form_at_hand_checked_indices():
    """d = 64, theta 50000, factor 64 over 4096: corr(32) = 8.91, corr(1) =
    19.16, so dimensions 0–8 keep their frequency, 20–31 are divided by
    64, and between them the ramp is (i − 8) / 12."""
    cfg = K.KimiConfig.kimi_share()
    table = np.asarray(cfg.rope_freqs, np.float64)
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    assert table.shape == (32,)
    np.testing.assert_allclose(table[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(table[20:], plain[20:] / 64, rtol=1e-6)
    for i, ramp in ((9, 1 / 12), (14, 0.5), (19, 11 / 12)):
        np.testing.assert_allclose(
            table[i], plain[i] * ((1 - ramp) + ramp / 64), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(R.yarn_table(cfg)[0]), table,
                               rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4
    assert cfg.softmax_scale == pytest.approx(m * m / math.sqrt(192))
    assert R.yarn_table(cfg)[1] == pytest.approx(cfg.softmax_scale)
    assert latent_attention.yarn_mscale(1.0) == 1.0


def test_plain_rope_is_not_yarn_past_the_original_length():
    cfg = K.KimiConfig.kimi_share()
    x = jax.random.normal(jax.random.key(6), (3, 64))
    near, far = jnp.asarray([0, 1, 2]), jnp.asarray([4096, 20000, 32767])
    plain = latent_attention.rope_interleaved(x, far, cfg.rope_theta)
    yarn = latent_attention.rope_interleaved(x, far, cfg.rope_theta,
                                             cfg.rope_freqs)
    assert not close(plain, yarn, 1e-2)
    # the fast dimensions (0–17 of the 64: pairs 0–8) turn alike anywhere
    assert close(plain[:, :18], yarn[:, :18], 1e-3)
    # position 0 is the identity under both
    both = [latent_attention.rope_interleaved(x, near, cfg.rope_theta, f)
            for f in (None, cfg.rope_freqs)]
    assert close(both[0][0], x[0], 1e-6) and close(both[1][0], x[0], 1e-6)


# --- the expert layer's three forms -------------------------------------------


def _expert_case(T=24, E=4, D=16, F=8, seed=7):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (E, D, 2 * F)) / 4,
            jax.random.normal(ks[2], (E, F, D)) / 3)


def _same_as_dense(x, idx, w, e_gu, e_down, first, tile, valid=None):
    grouped, rows = expert_share.held_part_grouped(
        x, idx, w, e_gu, e_down, first, jnp.float32, valid=valid, tile=tile)
    if valid is not None:
        w = jnp.where(valid[:, None], w, 0.0)
    dense = expert_share.held_part_dense(x, idx, w, e_gu, e_down, first,
                                         jnp.float32)
    assert close(grouped, dense, 1e-5)
    return int(rows)


@pytest.mark.parametrize("case", ["even", "skewed", "an expert with no rows",
                                  "every slot held", "none held",
                                  "padded rows"])
def test_the_grouped_form_computes_what_the_dense_form_computes(case):
    """Held experts 2..5 of a router of 12, top 3, tiles of 4 rows."""
    x, e_gu, e_down = _expert_case()
    T, first, tile = x.shape[0], 2, 4
    key = jax.random.key(11)
    w = jax.random.uniform(key, (T, 3)) + 0.1
    valid = None
    if case == "even":
        idx = jnp.stack([jax.random.permutation(jax.random.fold_in(key, t),
                                                12)[:3] for t in range(T)])
    elif case == "skewed":          # expert 3 takes every token, 5 one
        idx = jnp.tile(jnp.asarray([[3, 9, 10]]), (T, 1)).at[7, 1].set(5)
    elif case == "an expert with no rows":
        idx = jnp.tile(jnp.asarray([[2, 3, 5]]), (T, 1))        # never 4
    elif case == "every slot held":      # the static bound: T x k rows
        idx = jnp.tile(jnp.asarray([[2, 4, 5]]), (T, 1))
    elif case == "none held":
        idx = jnp.tile(jnp.asarray([[0, 1, 8]]), (T, 1))
    else:
        idx = jnp.tile(jnp.asarray([[2, 4, 9]]), (T, 1))
        valid = jnp.arange(T) < 17
    rows = _same_as_dense(x, idx.astype(jnp.int32), w, e_gu, e_down, first,
                          tile, valid)
    held = np.asarray(expert_share.held_slots(idx, first, 4))
    if valid is not None:
        held = held & np.asarray(valid)[:, None]
    per_expert = [int((np.asarray(idx)[held] == first + e).sum())
                  for e in range(4)]
    assert rows == sum(-(-n // tile) * tile for n in per_expert)
    assert rows >= held.sum() and (rows == 0) == (case == "none held")
    if case == "every slot held":
        assert rows == T * 3          # no slot dropped at the bound


def test_one_rule_picks_the_form_from_the_rows_a_held_expert_expects():
    """Grouped where a held expert expects at least half a tile of rows."""
    kimi = K.KimiConfig.kimi_share()
    chunk = kimi.prefill_chunk_tokens
    assert chunk * 8 / 384 > 64              # 85 rows an expert a chunk
    assert expert_share.prefill_form(chunk, kimi.routing) == "grouped"
    assert expert_share.prefill_form(1024, kimi.routing) == "dense"
    assert expert_share.prefill_form(3072, kimi.routing) == "grouped"
    assert expert_share.prefill_form(3071, kimi.routing) == "dense"
    pipe = pipeline_llm.LLMPipeline(kimi, None)
    assert pipe.prefill_plan(32768) == (4096, 8, "grouped")
    assert pipe.prefill_plan(1000) == (1000, 1, "dense")
    # held_part is that rule: both arms answer (y, rows multiplied)
    x, e_gu, e_down = _expert_case()
    idx = jnp.tile(jnp.asarray([[2, 4, 9]], jnp.int32), (24, 1))
    w = jnp.ones((24, 3))
    r = expert_share.Routing(12, 3, 1, 1, 1.0)
    y_g, rows_g = expert_share.held_part(x, idx, w, e_gu, e_down, 2,
                                         jnp.float32, r, tile=4)
    y_d, rows_d = expert_share.held_part(x, idx, w, e_gu, e_down, 2,
                                         jnp.float32, r, tile=128)
    assert expert_share.prefill_form(24, r, 4) == "grouped"
    assert close(y_g, y_d, 1e-5) and (int(rows_g), int(rows_d)) == (48, 96)


def test_the_parts_of_all_four_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: every share routes over all 16 (with the
    selection bias) and computes its own four, in each of the three forms;
    the shared expert is added once."""
    uncut = dataclasses.replace(CFG, n_routed_experts=16, first_expert=0)
    m = K.init_kimi(uncut, jax.random.key(8))["layers"][2]["moe"]
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
    other = dataclasses.replace(CFG, first_expert=8)
    a = K.prefill(CFG, params, ids, T)[0]
    b = K.prefill(other, params, ids, T)[0]
    assert not close(a, b)
    assert close(b, R.forward(other, params, ids)[0][-1])


def test_the_published_share_counts_what_the_issue_counted():
    cfg = K.KimiConfig.kimi_share()
    assert K.param_count(cfg) == 3_496_763_904
    tree = K.init_kimi(cfg, None, abstract=True)
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
    assert 6.50 < held / 2**30 < 6.52
    layer = tree["layers"][1]
    attention = sum(math.prod(a.shape)
                    for a in jax.tree_util.tree_leaves(layer["attn"]))
    assert attention == 101_124_096
    assert layer["moe"]["e_gu"].shape == (12, 7168, 4096)
    assert cfg.routing == expert_share.Routing(384, 8, 1, 1, 2.827)
    # 576 values a token a layer: 32 896 positions x 5 layers in bfloat16
    sizes = llm_model.cache_bytes(cfg.model, cfg, 32768 + 128)
    assert sizes == {"full": 5 * 32896 * 576 * 2}


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_scans_the_continuation_inside_one_labelled_program(
        params, ids, full_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is K.MODEL
    prefill, decode = pipe.programs(T, 8)
    assert pipe.programs(T, 8)[0] is prefill
    logits, cache, held, rows = prefill(ids)
    assert close(logits, full_logits[0][-1])
    assert cache["c"][0].shape[0] == 48            # three chunks of 16 rows
    text = str(jax.make_jaxpr(prefill.jitted)(pipe.params, ids))
    assert text.count("scan[") >= 1
    out = pipe.generate(np.asarray(ids).tolist(), 8, seed=3, temperature=0.7)
    again = pipe.generate(np.asarray(ids).tolist(), 8, seed=3,
                          temperature=0.7)
    assert out["ids"].tolist() == again["ids"].tolist() and out["finite"]
    assert out["prefill_chunks"] == 3 and out["prefill_form"] == "grouped"
    assert out["rows_prefill"] == int(np.asarray(rows).sum()) \
        >= int(out["held_prefill"].sum())
    assert out["cache_bytes"] == {"full": 5 * (T + 8) * 24 * 4}
    # a tap spacing of a parity tool's own leaves the drawn ids alone
    own = pipe.decode_fn(T, 8, tap_every=2)
    drawn, taps, _, _ = own(logits, cache, jax.random.key(3),
                            jnp.asarray(0.7, jnp.float32))
    assert np.asarray(drawn).tolist() == out["ids"].tolist()
    assert taps.shape == (4, CFG.vocab_size)


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["kimi-tiny"].kind == PRESETS["kimi-k2.6"].kind == "llm"
    assert PRESETS["kimi-k2.6"].llm == K.KimiConfig.kimi_share()
    assert PRESETS["kimi-k2.6"].llm.model is K.MODEL
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("kimi-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("kimi-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("kimi-tiny") is bundle
    from comfyui_distributed_tpu.cluster.residency import bundle_bytes

    assert bundle_bytes(bundle) == 4 * K.param_count(CFG)


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "kimi-tiny"
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
            "slots": {p: {k: tm.LLM_EXPERT_SLOTS.labels(where=k,
                                                        phase=p).value
                          for k in ("held", "absent")}
                      for p in ("prefill", "decode")},
            "rows": {f: tm.LLM_EXPERT_ROWS.labels(form=f).value
                     for f in ("grouped", "dense", "token")},
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
            moved = sum(after["slots"][phase].values()) \
                - sum(before["slots"][phase].values())
            assert moved == 3 * tokens * CFG.num_experts_per_tok * 4
        assert after["chunks"] - before["chunks"] == 3 * 3
        held = after["slots"]["prefill"]["held"] \
            - before["slots"]["prefill"]["held"]
        grouped = after["rows"]["grouped"] - before["rows"]["grouped"]
        # within the tiles' padding of the held slots, not experts x tokens
        assert held <= grouped <= held + 3 * 3 * 4 * 4 * (CFG.expert_tile
                                                          - 1)
        assert after["rows"]["dense"] == before["rows"]["dense"]
        assert after["rows"]["token"] - before["rows"]["token"] \
            == after["slots"]["decode"]["held"] \
            - before["slots"]["decode"]["held"]
        assert tm.LLM_CACHE_POSITIONS.labels().value == 48
        assert tm.LLM_CACHE_BYTES.labels(layers="full").value \
            == 5 * 48 * 24 * 4


def test_the_prefills_span_says_its_chunk(params, ids):
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.telemetry import spans

    if not telemetry.enabled():
        pytest.skip("telemetry is off")
    seen = []
    real = spans.span

    def spy(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    from comfyui_distributed_tpu.diffusion import pipeline

    pipe = pipeline_llm.LLMPipeline(CFG, params)
    prefill = pipe.prefill_fn(T, 8)
    try:
        spans.span = spy
        prefill(ids)
    finally:
        spans.span = real
    assert ("pipeline_call", {"pipeline": "llm_prefill", "chunk": 16}) \
        in seen
    assert pipeline.bind_weights.__doc__.count("span_attrs") == 1


# --- the benchmark's files ----------------------------------------------------


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "kimi-k2.6.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "kimi" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    # the file's ``attn_block_q/k`` are PR 32's tile: documentation no code
    # reads, the benchmark's to correct (PERF.md §7); the served tile is
    # the preset's alone
    shared = [k for k in fields
              if k in held and not k.startswith("attn_block_")]
    assert len(shared) >= 27
    for key in shared:
        assert held[key] == fields[key], key
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == K.param_count(preset.llm)
    assert held["llm"]["bytes"] == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in
        jax.tree_util.tree_leaves(K.init_kimi(preset.llm, None,
                                              abstract=True)))
    assert sum(n * (4 if "each of 4" in part else 1) for part, n in
               held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert "32 chips share each layer" in held["deployment"]
    assert held["router_experts"] == held["published"]["n_routed_experts"] \
        == 32 * held["n_routed_experts"]
    assert held["published"]["vocab_size"] == 8 * held["vocab_size"]
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    assert held["trace_phases"]["llm_prefill"] == "jit_llm_prefill"
    assert held["trace_phases"]["llm_decode"] == "jit_llm_decode"
    assert set(held["reduced"]) == set(held["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "vision_tower"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-k2.6")
    assert entry["reduced"] == held["reduced"]
    assert entry["source"] == held["source"]
    # every number of the catalog's config, under its key, but the reduced
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"Kimi-K2.6"' in line)
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in held["reduced"]:
                assert held[key] == value, key
        scaling = catalog["config"]["rope_scaling"]
        assert (fields["rope_factor"], fields["rope_original_len"],
                fields["rope_beta_fast"], fields["rope_beta_slow"],
                fields["rope_mscale"], fields["rope_mscale_all_dim"]) == (
            scaling["factor"], scaling["original_max_position_embeddings"],
            scaling["beta_fast"], scaling["beta_slow"], scaling["mscale"],
            scaling["mscale_all_dim"])


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_kimi_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_kimi_reference.py").read_bytes()
    assert repo == copy


def _cell(rehearsal=False):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import workload

    return workload.assemble(CELL, rehearsal=rehearsal)


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench.kinds.kimi import request_sizes

    cell = _cell()
    assert cell.preset == "kimi-k2.6" and cell.chips == 1
    assert request_sizes(cell) == (32768, 128)
    assert cell.graph["9"]["inputs"]["temperature"] == 0.7
    assert (cell.steps, cell.cfg, cell.step_key) == (8, 6.0, "1024x1024.b2")
    assert cell.image_hw == (1024, 1024)
    assert cell.traffic["clients"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.config["serve_env"] == {}
    small = _cell(rehearsal=True)
    assert small.preset == "kimi-tiny"
    assert small.graph["1"]["inputs"]["ckpt_name"] == "tiny"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"kimi_prefill_ms", "kimi_decode_ms_per_token",
                     "kimi_share_pct", "kimi_prefill_mfu_pct",
                     "kimi_attn_core_mxu_pct", "kimi_attn_core_pct",
                     "kimi_decode_hbm_pct", "kimi_held_slot_pct",
                     "denoise_ms_per_step", "peak_hbm_gib"}
    # Ling's own readers (keyed by kind) stay out; the four keyed by device
    # scope (PR 34) serve every rewriter
    scoped = {"llm_attn_pct", "llm_experts_pct", "llm_ffn_pct",
              "llm_head_sample_pct"}
    assert scoped <= names
    assert not {n for n in names - scoped
                if n.startswith(("llm_", "motif_"))}
    for other in ("motif-3-beta.reprompt1k-sdxl8",
                  "ling-3.0-flash-vl.reprompt1k", "sdxl-base.solo30"):
        import cdtbench.workload as workload

        assert not {m["name"] for m in workload.assemble(other).metrics(
            "per_layer")} & {n for n in names if n.startswith("kimi_")}


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    """``prefill_flops`` and ``attention_core_flops`` give ISSUE 32's
    counts at 32 768 tokens; ``decode_bytes_per_token`` is written from the
    configuration's sizes and the model's own weight tree must give the
    same bytes."""
    from cdtbench.kinds.kimi import (attention_core_flops,
                                     decode_bytes_per_token, prefill_flops)

    cell = _cell()
    core = attention_core_flops(cell.config, 32768)
    assert core == pytest.approx(5 * 21.99e12, rel=1e-3)
    even = 32768 * 8 * 4 * 12 / 384            # held slots, routing even
    assert prefill_flops(cell.config, 32768, even) == pytest.approx(
        184.2e12, rel=1e-3)
    # a slot more is one row of one expert more
    assert prefill_flops(cell.config, 32768, even + 1) \
        - prefill_flops(cell.config, 32768, even) == pytest.approx(
            2 * 3 * 7168 * 2048)
    cfg = K.KimiConfig.kimi_share()
    tree = K.init_kimi(cfg, None, abstract=True)
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
    cache = 5 * (32768 + 64) * 576 * 2
    want = fixed + cache + 0.03125 * 8 * expert
    got = decode_bytes_per_token(cell.config, 0.03125, 32768, 128)
    assert abs(got - want) / want < 1e-6
    assert 2.70e9 < got < 2.80e9                   # the issue's 2.75 GB


def _snapshot(held, absent, seconds):
    def slots(where, phase, value):
        return {"labels": {"where": where, "phase": phase}, "value": value}

    return {
        "cdt_llm_expert_slots_total": {"series": [
            slots("held", "decode", held), slots("absent", "decode", absent),
            slots("held", "prefill", 256 * held),
            slots("absent", "prefill", 256 * absent)]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 3 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock():
    from cdtbench import readers
    from cdtbench.kinds.kimi import (attention_core_flops,
                                     decode_bytes_per_token, prefill_flops)

    cell = _cell()
    slots = 2 * 128 * 32                       # two requests' decode slots
    held = slots // 32
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 3.6}] * 2,
           "opened": _snapshot(10, 90, 1.0),
           "closed": _snapshot(10 + held, 90 + slots - held,
                               1.0 + 2 * 0.576),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 3.2,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 0.56, "count": 1},
                         "llm_prefill": {"seconds": 1.75, "count": 1}},
                     "op_seconds": {"latent_causal_mha.1": 0.5,
                                    "latent_causal_mha.2": 0.3,
                                    "fusion.7": 1.0}}}
    assert readers.read("kimi_decode_ms_per_token", ctx) \
        == pytest.approx(4.5)
    assert readers.read("kimi_prefill_ms", ctx) == pytest.approx(1728.0)
    assert readers.read("kimi_share_pct", ctx) == pytest.approx(
        100 * 4 * 1.152 / 7.2)
    need = decode_bytes_per_token(cell.config, 1 / 32, 32768, 128)
    assert readers.read("kimi_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / (0.56 / 128), rel=1e-6)
    flops = prefill_flops(cell.config, 32768, 256 * held / 2)
    assert readers.read("kimi_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 1.75, rel=1e-6)
    assert readers.read("kimi_attn_core_mxu_pct", ctx) == pytest.approx(
        100 * attention_core_flops(cell.config, 32768) / 197e12 / 0.8,
        rel=1e-6)
    assert readers.read("kimi_attn_core_pct", ctx) == pytest.approx(25.0)
    assert readers.read("kimi_held_slot_pct", ctx) == pytest.approx(
        100 / 32)
    # no trace, a trace without the kernel (the lax schedule shipped, or
    # the parent), or a program without the series: nothing, not zero
    for name in ("kimi_decode_hbm_pct", "kimi_prefill_mfu_pct",
                 "kimi_attn_core_mxu_pct", "kimi_attn_core_pct"):
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    for name in ("kimi_attn_core_mxu_pct", "kimi_attn_core_pct"):
        assert readers.read(name, {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("kimi_decode_hbm_pct", "kimi_decode_ms_per_token",
                 "kimi_prefill_ms", "kimi_held_slot_pct", "kimi_share_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of them
    import cdtbench.workload as workload

    motif = workload.assemble("motif-3-beta.reprompt1k-sdxl8")
    for name in ("kimi_decode_hbm_pct", "kimi_decode_ms_per_token",
                 "kimi_share_pct", "kimi_prefill_mfu_pct",
                 "kimi_attn_core_mxu_pct"):
        assert readers.read(name, {**ctx, "cell": motif}) is None, name


@pytest.mark.parametrize("arm", ["cache_fp8", "experts_fp8", "plain_rope",
                                 "no_mscale"])
def test_the_parity_tools_lower_arms_change_what_the_program_computes(
        params, arm):
    """The four arms that must fail on the chip are built around the
    served code: here they only have to move the logits, and leave no
    trace."""
    import contextlib
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_kimi

    ids40 = [int(i) % CFG.vocab_size for i in range(3, 43)]

    def run(cfg, weights, around=contextlib.nullcontext):
        with around():
            return pipeline_llm.LLMPipeline(cfg, weights).generate(
                ids40, 8, 1, 0.7)

    sound = run(CFG, params)
    if arm == "cache_fp8":
        low = run(CFG, params, parity_kimi.cache_in_fp8)
    elif arm == "experts_fp8":
        fp8 = parity_kimi.experts_in_fp8(params)
        assert fp8["layers"][1]["moe"]["e_gu"].dtype == jnp.float8_e4m3fn
        assert fp8["layers"][0] is params["layers"][0]
        low = run(CFG, fp8)
    else:
        cut = parity_kimi.left_out(CFG, arm)
        assert dataclasses.asdict(cut) == dataclasses.asdict(CFG)
        assert cut.model is K.MODEL
        assert (cut.softmax_scale != CFG.softmax_scale) == (arm
                                                            == "no_mscale")
        assert np.allclose(cut.rope_freqs, CFG.rope_freqs) \
            == (arm == "no_mscale")
        low = run(cut, params)
    assert not close(low["prefill_logits"], sound["prefill_logits"], 1e-4)
    again = run(CFG, params)
    assert np.array_equal(np.asarray(again["prefill_logits"]),
                          np.asarray(sound["prefill_logits"]))


def test_the_parity_tool_rehearses_and_its_reference_is_the_repos(params):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_kimi

    assert parity_kimi.TAP_EVERY < pipeline_llm.TAP_EVERY == 128
    assert close(parity_kimi.load_reference().forward(
        CFG, params, jnp.arange(16))[0], R.forward(
        CFG, params, jnp.arange(16))[0], 1e-6)
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "kimi-k2.6.parity.json").read_text())["limits"]
    assert set(limits) == {"best_decode_row_rel_l2", "median_row_rel_l2",
                           "worst_row_rel_l2"}
    assert all(v["limit"] > 0 and len(v["reason"]) > 40
               for v in limits.values())


def test_the_golden_names_a_request_and_holds_an_image():
    spec = json.loads((ROOT / "cdtbench" / "goldens"
                       / f"{CELL}.json").read_text())
    assert set(spec["request"]) == {"seed", "prompt"}
    assert spec["max_mean_abs_levels"] == 2.0 and spec["stride"] == 4
    from PIL import Image

    image = Image.open(ROOT / "cdtbench" / "goldens" / f"{CELL}.png")
    assert image.size == (256, 256)
