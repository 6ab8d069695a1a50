"""The tenth prompt rewriter (attention inside a compressed latent behind two
causal convolutions, K/V rows AND three recurrent tails in one attention
layer, a top-1 router that is an MLP carrying its state from layer to layer,
EVERY expert held, a scaled and shifted residual stream, a tied head) at the
tiny float32 preset, against the plain reference on seeded weights — logits,
not tokens: the chunked prefill with a PADDED last chunk and decode through
rows and tails, every piece left out one at a time, the router handed logits,
the expert share, the streamed kernel's inner-width axis in the interpreter,
the shared pipeline, the nodes, the shipped graph, and the benchmark's files,
counts and readers of the cell."""

import dataclasses
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.models import llm_zaya as Z
from comfyui_distributed_tpu.models import llm_zaya_reference as R
from comfyui_distributed_tpu.ops import expert_share

ROOT = Path(__file__).resolve().parent.parent
# a float32 program against the float32 reference: logits of unit scale
# through 3 layers: 2.8e-6 measured; 1e-4 is far under what any left-out
# piece reads (the smallest, the selection bias: 2e-3)
F32_TOL = 1e-4
CFG = Z.ZayaConfig.tiny()
CELL = "zaya1-8b.ctx128k-sdxl8"
T, NEW = 37, 8


@pytest.fixture(scope="module")
def params():
    return Z.init_zaya(CFG, jax.random.key(0))


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


def through_the_cache(cfg, params, ids, chunk=None, kernel=None):
    """Logits at every position: a chunked prefill of ``T`` tokens (every
    position's), then ``NEW`` decode steps."""
    logits, cache, _ = Z.prefill(cfg, params, ids[:T], T + NEW,
                                 all_logits=True, chunk=chunk, kernel=kernel)
    rows = [logits]
    _, cache, _ = Z.prefill(cfg, params, ids[:T], T + NEW, chunk=chunk,
                            kernel=kernel)
    for t in range(T, T + NEW):
        step, cache, _ = Z.decode_step(cfg, params, cache, ids[t], t)
        rows.append(step[None])
    return jnp.concatenate(rows)


# --- the model against the reference ------------------------------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 3
    assert CFG.moe_layers == [0, 1, 2]             # every layer routes
    assert CFG.num_experts == CFG.router_experts   # and holds them all
    assert T > 2 * CFG.prefill_chunk_tokens and T % CFG.prefill_chunk_tokens
    assert CFG.rotary_dim == CFG.head_dim // 2
    assert CFG.routing == expert_share.Routing(
        4, 1, 1, 1, 1.0, score="softmax", normalised=False)
    full = Z.ZayaConfig.zaya_share()
    assert full.routing == expert_share.Routing(
        16, 1, 1, 1, 1.0, score="softmax", normalised=False)
    assert (full.q_width, full.latent_width, full.rotary_dim,
            full.tail_width) == (1024, 1280, 64, 2688)
    assert (full.num_experts, full.first_expert) == (16, 0)
    # exactly at the streamed form's edge: 4096 rows x top 1 = 256 x 16
    assert 4096 * 1 == full.expert_tile * full.router_experts
    with pytest.raises(ValueError, match="two taps"):
        Z.ZayaConfig.tiny(cca_time0=3)


@pytest.mark.parametrize("kernel, chunk", [("lax", 16), ("lax", 10),
                                           ("lax", T), ("interpret", 16)])
def test_prefill_and_decode_through_the_cache_are_the_reference(
        params, ids, full_logits, chunk, kernel):
    """At every position: chunks that do not divide the prompt (the last
    one padded), then decode from the K/V rows and the three tails; the
    kernels in the interpreter at the preset's chunk, which their tiles
    divide."""
    got = through_the_cache(CFG, params, ids, chunk, kernel)
    assert close(got, full_logits)


def test_a_padded_last_chunk_leaves_rows_and_tails_as_the_whole_prefill_does(
        params, ids):
    """The recurrent-leaf contract inside an attention layer: after a
    prefill whose last chunk holds 5 valid rows of 16, the K/V rows of the
    prompt AND all three tails are what one chunk of the whole prompt
    leaves; rows past the prompt may hold anything."""
    _, whole, _ = Z.prefill(CFG, params, ids[:T], 48, chunk=T)
    _, walked, _ = Z.prefill(CFG, params, ids[:T], 48)
    for name in ("k", "v"):
        for a, b in zip(whole[name], walked[name]):
            assert close(a[:, :T], b[:, :T], 1e-5)
    Zw, d = CFG.latent_width, CFG.head_dim
    for a, b in zip(whole["tails"], walked["tails"]):
        assert a.shape == (2 * Zw + d,) and close(a, b, 1e-5)
        assert float(jnp.abs(a[:Zw]).max()) > 0      # z of the last token
    # and they are token T−1's: a prompt one token longer moves all three
    _, longer, _ = Z.prefill(CFG, params, ids[:T + 1], 48)
    assert not close(longer["tails"][0], walked["tails"][0], 1e-3)
    kinds = Z.cache_kinds(CFG, walked)
    assert set(kinds) == {"kv", "tails"}


def test_the_reference_in_query_blocks_is_itself(params, ids, full_logits):
    blocks, _ = R.forward(CFG, params, ids, block=7)
    assert close(blocks, full_logits, 1e-5)
    some, _ = R.forward(CFG, params, ids, positions=[3, T - 1, T + 2])
    assert close(some, full_logits[jnp.asarray([3, T - 1, T + 2])], 1e-6)


def test_a_bfloat16_run_is_within_its_stated_limit_and_over_float32s(
        params, ids, full_logits):
    """bfloat16 operands and K/V rows: the median position lies under 3e-2 of
    the reference's norm (1.1e-2 measured here; the chip's limits are
    ``zaya1-8b.parity.json``'s) — the median, because with top 1 a flipped
    expert replaces a token's WHOLE expert output and one position may read
    ten times that — and the float32 tolerance refuses it."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    got = np.asarray(through_the_cache(cfg, params, ids), np.float64)
    want = np.asarray(full_logits, np.float64)
    rel = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    assert not close(got, want) and 1e-3 < np.median(rel) < 3e-2
    assert rel.max() < 0.5


def _without(params, part, leaf, value):
    return {**params, "layers": [
        {**layer, part: {**layer[part], leaf: jnp.full_like(
            layer[part][leaf], value)}} for layer in params["layers"]]}


LEFT_OUT = {
    "the first convolution": lambda p: _without(
        _without(p, "attn", "conv0_b", 0.0), "attn", "conv0_w",
        jnp.asarray([[0.0], [1.0]])),
    "the temperature": lambda p: _without(p, "attn", "log_temp", 0.0),
    "the EDA add": lambda p: _without(p, "router", "eda", 0.0),
    "the selection bias": lambda p: _without(p, "router", "bias", 0.0),
    "the stream's residual scale": lambda p: _without(
        _without(p, "res_attn", "s_h", 1.0), "res_moe", "s_h", 1.0),
    "the sublayers' residual scale": lambda p: _without(
        _without(p, "res_attn", "s_y", 1.0), "res_moe", "s_y", 1.0),
    "the residual biases": lambda p: _without(
        _without(p, "res_attn", "b_y", 0.0), "res_moe", "b_h", 0.0)}


@pytest.mark.parametrize("piece", sorted(LEFT_OUT))
def test_a_parameter_at_its_usual_initialisation_moves_the_logits(
        params, ids, full_logits, piece):
    """Each is drawn AWAY from 1 or 0, so that the program with the piece at
    its neutral value — its mathematics left out — fails the comparison."""
    got = through_the_cache(CFG, LEFT_OUT[piece](params), ids)
    assert not close(got, full_logits, 10 * F32_TOL), piece


def _patched_mix(monkeypatch, change):
    """The program with ``change(q, k, v, z, vv, cfg, p, tails, rope)``
    applied to what ``_cca_mix`` answers."""
    mix = Z._cca_mix

    def other(cfg, p, z, vv, tails, rope):
        q, k, v, c0 = mix(cfg, p, z, vv, tails, rope)
        return (*change(q, k, v, cfg=cfg, p=p, z=z, vv=vv, tails=tails,
                        rope=rope), c0)

    monkeypatch.setattr(Z, "_cca_mix", other)


def _turned(a, rope, lo, hi, back=False):
    """Dimensions ``lo .. hi`` of ``a`` (q [T,H,d] or k [G,T,d]) turned by
    the rows' angles (``back``: by their negatives)."""
    cos, sin = (r[:, None] if a.shape[0] == r.shape[0] else r[None]
                for r in rope)
    sin = -sin if back else sin
    x1, x2 = jnp.split(a[..., lo:hi], 2, axis=-1)
    return jnp.concatenate([a[..., :lo], x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin, a[..., hi:]], -1)


CODE_LEFT_OUT = {
    # v's second head unshifted: the token's own x W_v2
    "the value shift": lambda q, k, v, cfg, vv, **_: (
        q, k, jnp.stack([vv[:, :cfg.head_dim], vv[:, cfg.head_dim:]])),
    "rope on half (none)": lambda q, k, v, cfg, rope, **_: (
        *(_turned(a, rope, 0, cfg.rotary_dim, back=True) for a in (q, k)),
        v),
    # the SECOND half roped too (by the same angles)
    "rope on half (all)": lambda q, k, v, cfg, rope, **_: (
        *(_turned(a, rope, cfg.rotary_dim, cfg.head_dim) for a in (q, k)),
        v)}


@pytest.mark.parametrize("piece", sorted(CODE_LEFT_OUT))
def test_a_piece_of_the_mix_left_out_moves_the_logits(
        params, ids, full_logits, piece, monkeypatch):
    _patched_mix(monkeypatch, CODE_LEFT_OUT[piece])
    got = through_the_cache(CFG, params, ids)
    assert not close(got, full_logits, 10 * F32_TOL), piece


def test_the_second_convolution_is_in_the_program(params, ids, full_logits):
    changed = _without(_without(params, "attn", "conv1_w", 0.0), "attn",
                       "conv1_b", 0.0)
    assert not close(through_the_cache(CFG, changed, ids), full_logits,
                     10 * F32_TOL)


def test_the_q_k_mean_is_in_the_program(params, ids, full_logits,
                                        monkeypatch):
    """``q = c1 + mean``: the mean of the UNconvolved latents taken out again
    just ahead of the norm (``_l2`` is called once a head, the query heads
    first) is not the reference."""
    H, G, d = CFG.num_attention_heads, CFG.num_key_value_heads, CFG.head_dim
    J, mix, l2, seen = H // G, Z._cca_mix, Z._l2, {}

    def keeping_z(cfg, p, z, vv, tails, rope):
        seen.update(z=z, head=0)
        return mix(cfg, p, z, vv, tails, rope)

    def cut(n):
        return seen["z"][:, n * d:(n + 1) * d]

    def without_the_mean(x, eps):
        n = seen["head"]
        seen["head"] = n + 1
        if n < H:
            mean = 0.5 * (cut(n) + cut(H + n // J))
        else:
            g = n - H
            mean = 0.5 * (sum(cut(g * J + j) for j in range(J)) / J
                          + cut(H + g))
        return l2(x - mean, eps)

    monkeypatch.setattr(Z, "_cca_mix", keeping_z)
    monkeypatch.setattr(Z, "_l2", without_the_mean)
    assert not close(through_the_cache(CFG, params, ids), full_logits,
                     10 * F32_TOL)
    assert seen["head"] == H + G


# --- the router: logits handed in ----------------------------------------------


NINE = {"ling": expert_share.Routing(512, 8, 8, 4, 2.5),
        "motif": expert_share.Routing(384, 8, 1, 1, 2.0),
        "kimi": expert_share.Routing(384, 8, 1, 1, 2.827),
        "trinity": expert_share.Routing(256, 4, 1, 1, 2.448),
        "longcat": expert_share.Routing(512, 12, 1, 1, 6.0, score="softmax",
                                        normalised=False, zero_experts=256),
        "glm": expert_share.Routing(256, 8, 1, 1, 2.5),
        "keye": expert_share.Routing(128, 8, 1, 1, 1.0, score="softmax"),
        "ling-tiny": expert_share.Routing(32, 4, 4, 2, 2.5),
        "keye-tiny": expert_share.Routing(8, 2, 1, 1, 1.0, score="softmax")}


@pytest.mark.parametrize("name", sorted(NINE))
@pytest.mark.parametrize("biased", [False, True])
def test_route_is_the_linear_case_of_route_logits_bit_for_bit(name, biased):
    r = NINE[name]
    keys = jax.random.split(jax.random.key(len(name)), 3)
    x = jax.random.normal(keys[0], (24, 48))
    w_router = jax.random.normal(keys[1], (48, r.outputs)) / 7
    bias = 0.02 * jax.random.normal(keys[2], (r.outputs,)) if biased else None
    idx, w = expert_share.route(x, w_router, bias, r)
    logits = jnp.dot(x, w_router, precision=jax.lax.Precision.HIGHEST)
    idx2, w2 = expert_share.route_logits(logits, bias, r)
    assert np.array_equal(np.asarray(idx), np.asarray(idx2))
    assert np.array_equal(np.asarray(w), np.asarray(w2))
    assert idx.shape == (24, r.per_token) and idx.dtype == jnp.int32


def test_top_one_of_a_softmax_hands_back_the_unnormalised_probability():
    r = Z.ZayaConfig.zaya_share().routing
    logits = jax.random.normal(jax.random.key(4), (50, 16)) * 2
    bias = jnp.zeros((16,)).at[5].set(0.3)
    idx, w = expert_share.route_logits(logits, bias, r)
    p = jax.nn.softmax(logits)
    assert np.array_equal(np.asarray(idx[:, 0]),
                          np.asarray(jnp.argmax(p + bias, -1)))
    assert np.allclose(np.asarray(w[:, 0]),
                       np.asarray(p[jnp.arange(50), idx[:, 0]]))
    assert float(w.max()) < 1.0 and float(w.min()) > 0.0   # not normalised
    # the bias moved a selection and no weight
    plain, _ = expert_share.route_logits(logits, None, r)
    assert int((plain != idx).sum()) > 0


def test_the_router_state_goes_from_layer_to_layer_inside_one_forward(
        params, ids):
    """Layer ``l``'s logits read ``r`` of layer ``l − 1`` AFTER its own add:
    the program's state equals the reference's, layer by layer."""
    x = jax.random.normal(jax.random.key(5), (6, CFG.hidden_size))
    state_p = state_r = None
    for layer in params["layers"]:
        logits, state_p = Z._router_logits(CFG, layer["router"], x, state_p)
        with jax.default_matmul_precision("highest"):
            prob, state_r = R.router(CFG, layer["router"], x, state_r)
        assert close(state_p, state_r, 1e-5)
        assert close(jax.nn.softmax(logits), prob, 1e-5)


# --- the expert share ------------------------------------------------------------


def test_the_parts_of_two_half_shares_add_up_to_the_uncut_layer(params, ids):
    """The 4 experts cut into two shares of 2: the two partial results of
    the REFERENCE add up to the uncut reference's layer (nothing is computed
    by both, and every token's one slot is held by exactly one)."""
    whole, counts = R.forward(CFG, params, ids)
    assert [int(c) for c in counts] == [T + NEW] * 3
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.key(10), (64, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        prob, _ = R.router(CFG, layer["router"], x, None)
        bias = layer["router"]["bias"]
        want, held = R.experts(CFG, layer["moe"], prob, bias, x, (0, 4))
        parts = [R.experts(CFG, layer["moe"], prob, bias, x, (first, 2))
                 for first in (0, 2)]
    assert close(parts[0][0] + parts[1][0], want, 1e-6)
    assert int(parts[0][1]) + int(parts[1][1]) == int(held) == 64
    assert 0 < int(parts[0][1]) < 64
    # and the served forms of the WHOLE router are that layer
    logits, _ = Z._router_logits(CFG, layer["router"], x, None)
    idx, w = expert_share.route_logits(logits, bias, CFG.routing)
    m = layer["moe"]
    grouped, rows = expert_share.held_part_by_shape(
        x, idx, w, m["e_gu"], m["e_down"], 0, jnp.float32, CFG.routing,
        tile=CFG.expert_tile, kernel="lax")
    assert close(grouped, want) and int(rows) % CFG.expert_tile == 0
    token = jnp.stack([expert_share.held_part_token(
        x[t], idx[t], w[t], m["e_gu"], m["e_down"], 0, jnp.float32)
        for t in range(64)])
    assert close(token, want, 1e-5)


@pytest.mark.parametrize("case", ["even", "one takes all", "padded chunk"])
def test_the_streamed_form_at_sixteen_wide_experts_is_the_grouped_loop(case):
    """``E`` 16, ``F`` 2048 in the Pallas interpreter against
    ``held_part_grouped``: the same sum and the same rows multiplied, at
    even and at one-expert-takes-all routing."""
    keys = jax.random.split(jax.random.key(13), 4)
    n, D, F, E, tile = 64, 16, 2048, 16, 4
    x = jax.random.normal(keys[0], (n, D))
    e_gu = jax.random.normal(keys[1], (E, D, 2 * F)) / 4
    e_down = jax.random.normal(keys[2], (E, F, D)) / 45
    idx = (jnp.arange(n) % E)[:, None].astype(jnp.int32)
    if case == "one takes all":
        idx = jnp.full((n, 1), 11, jnp.int32)
    w = jax.random.uniform(keys[3], (n, 1), jnp.float32, 0.1, 1.0)
    valid = jnp.arange(n) < 50 if case == "padded chunk" else None
    want, want_rows = expert_share.held_part_grouped(
        x, idx, w, e_gu, e_down, 0, jnp.float32, valid=valid, tile=tile)
    got, rows = expert_share.held_part_streamed(
        x, idx, w, e_gu, e_down, 0, jnp.float32, valid=valid, tile=tile,
        kernel="interpret")
    assert bool(jnp.isfinite(got).all())
    assert np.allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert int(rows) == int(want_rows) and int(rows) % tile == 0


def test_the_forms_rule_answers_what_the_chip_settled():
    """At this geometry (4096 rows x top 1 over 16 held experts of 2048 x
    2048: exactly at the streamed form's edge, where the kernel read 1.74 to
    2.08 ms a chunk and the loop 2.40 to 3.16 — PERF.md section 6, PR 57)
    and, unchanged, at the ninth module's."""
    from comfyui_distributed_tpu.models.registry import PRESETS

    full = Z.ZayaConfig.zaya_share()
    assert expert_share.prefill_form(4096, full.routing,
                                     full.expert_tile) == "grouped"
    assert expert_share.streamed_form(4096, 16, full.routing,
                                      full.expert_tile)
    assert not expert_share.streamed_form(2048, 16, full.routing,
                                          full.expert_tile)
    keye = PRESETS["keye-vl-2.0-30b-a3b"].llm
    assert expert_share.streamed_form(4096, 128, keye.routing,
                                      keye.expert_tile)
    assert not expert_share.streamed_form(4096, 64, keye.routing, 256)
    pipe = pipeline_llm.LLMPipeline(full, None)
    assert pipe.prefill_plan(130944) == (4096, 32, "grouped")


def test_the_published_cut_counts_what_the_issue_counted():
    cfg = Z.ZayaConfig.zaya_share()
    assert Z.param_count(cfg) == 2_612_970_164
    tree = Z.init_zaya(cfg, None, abstract=True)
    layer = tree["layers"][1]
    per_layer = sum(math.prod(a.shape)
                    for a in jax.tree_util.tree_leaves(layer))
    assert per_layer == 207_583_506
    attn = layer["attn"]
    assert sum(math.prod(attn[n].shape)
               for n in ("w_qk", "w_v", "w_o")) == 5_242_880
    assert sum(math.prod(attn[n].shape) for n in (
        "conv0_w", "conv0_b", "conv1_w", "conv1_b")) == 332_800
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
        layer["router"])) == 660_736 + 16           # + the selection bias
    assert layer["moe"]["e_gu"].shape == (16, 2048, 4096)
    assert layer["moe"]["e_down"].shape == (16, 2048, 2048)
    assert tree["embed"].shape == (262272, 2048) and "head" not in tree
    assert tree["rope"]["cos"].shape == (131072, 32)
    # published: 40 layers and the vocabulary
    assert 40 * per_layer + 262272 * 2048 + 2048 == 8_840_475_344
    # 1024 B a token a layer; 2688 float32 values of tails a layer
    sizes = llm_model.cache_bytes(cfg.model, cfg, 130944 + 128)
    assert sizes == {"kv": 10 * 131072 * 1024, "tails": 10 * 2688 * 4}
    pairs = cfg.attended_keys(130944, 128)
    assert pairs[("cca", "prefill")] == 10 * (130944 * 130945 // 2)
    assert pairs[("cca", "decode")] == 10 * sum(range(130945, 131073))
    assert 130944 == 31 * 4096 + 3968                # a padded last chunk


def test_the_rope_table_is_the_fifth_rewriters_at_the_roped_half(params):
    from comfyui_distributed_tpu.models.llm_trinity import rope_table

    cos, sin = R.rope_angles(CFG, CFG.max_position_embeddings)
    assert np.array_equal(np.asarray(params["rope"]["cos"]), np.asarray(cos))
    assert np.array_equal(np.asarray(params["rope"]["sin"]), np.asarray(sin))
    assert cos.shape == (CFG.max_position_embeddings, CFG.rotary_dim // 2)
    whole = rope_table(dataclasses.replace(CFG, head_dim=CFG.rotary_dim))
    assert np.array_equal(np.asarray(whole["cos"]), np.asarray(cos))


def test_seeded_weights_are_drawn_away_from_their_usual_initialisation(
        params):
    layer = params["layers"][0]
    for part, leaf, mean in (("attn", "log_temp", 0.7),
                             ("router", "eda", 0.5),
                             ("res_attn", "s_h", 1.0),
                             ("res_moe", "s_y", 1.0),
                             ("attn", "conv0_w", 0.5)):
        a = np.asarray(layer[part][leaf])
        assert a.std() > 0.02 and abs(a.mean() - mean) < 0.35, (part, leaf)
    for part, leaf in (("router", "bias"), ("router", "b_down"),
                       ("attn", "conv1_b"), ("res_attn", "b_h")):
        a = np.asarray(layer[part][leaf])
        assert a.std() > 0.005 and abs(a.mean()) < 0.1, (part, leaf)
    assert np.all(np.asarray(layer["norm1"]) == 1.0)
    # the same seed, the same weights
    again = Z.init_zaya(CFG, jax.random.key(0))
    assert np.array_equal(np.asarray(again["layers"][2]["router"]["eda"]),
                          np.asarray(params["layers"][2]["router"]["eda"]))


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_scans_the_continuation_inside_one_labelled_program(
        params, ids, full_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is Z.MODEL
    assert pipe.prefill_plan(T) == (16, 3, "grouped")
    prefill, decode = pipe.programs(T, 8)
    logits, cache, held, rows = prefill(ids[:T])
    assert close(logits, full_logits[T - 1])
    assert held.tolist() == [T] * 3 and rows.shape == (3,)
    out, taps, slots, finite = decode(logits, cache, jax.random.key(3),
                                      jnp.asarray(0.7, jnp.float32))
    assert out.shape == (8,) and bool(finite)
    assert slots.tolist() == [8] * 3               # one slot a token a layer


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["zaya-tiny"].kind == PRESETS["zaya1-8b"].kind == "llm"
    assert PRESETS["zaya1-8b"].llm == Z.ZayaConfig.zaya_share()
    assert PRESETS["zaya1-8b"].llm.model is Z.MODEL
    assert PRESETS["zaya-tiny"].llm == CFG
    at = list(PRESETS).index("zaya1-8b")
    assert list(PRESETS)[at:at + 2] == ["zaya1-8b", "zaya-tiny"]
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("zaya-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("zaya-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("zaya-tiny") is bundle


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "zaya-tiny"
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
            "keys": {p: tm.LLM_ATTN_KEYS.labels(layers="cca", phase=p).value
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
        layers = CFG.num_hidden_layers
        for phase, tokens in (("prefill", 40), ("decode", 8)):
            # one slot a token a layer, every one held: none is ever absent
            assert after["slots"][("held", phase)] \
                - before["slots"][("held", phase)] == 3 * tokens * layers
            assert after["slots"][("absent", phase)] \
                == before["slots"][("absent", phase)]
        grouped = after["rows"]["grouped"] - before["rows"]["grouped"]
        assert grouped >= 3 * 40 * layers and grouped % CFG.expert_tile == 0
        assert after["rows"]["token"] - before["rows"]["token"] \
            == 3 * 8 * layers
        assert after["chunks"] - before["chunks"] == 3 * 3
        want = CFG.attended_keys(40, 8)
        assert want[("cca", "prefill")] == layers * 40 * 41 // 2
        for phase in ("prefill", "decode"):
            assert after["keys"][phase] - before["keys"][phase] \
                == 3 * want[("cca", phase)]
        assert tm.LLM_CACHE_POSITIONS.labels().value == 48
        # 48 rows x 2 K/V heads x 8 x (k, v) x 4 B a layer; the tails
        assert tm.LLM_CACHE_BYTES.labels(layers="kv").value \
            == layers * 48 * 2 * 8 * 2 * 4
        assert tm.LLM_CACHE_BYTES.labels(layers="tails").value \
            == layers * CFG.tail_width * 4


def test_the_two_pieces_are_told_apart_below_the_attention_scope(params,
                                                                 ids):
    """Every operation of the mix and of the core carries its plain named
    scope BELOW ``cdt.llm_attn``; the router's MLP is a device layer."""
    text = jax.jit(lambda i: Z.prefill(CFG, params, i, T + NEW)).lower(
        ids[:T]).compile().as_text()
    for scope in ("llm_cca_mix", "llm_cca_core"):
        assert re.search(r"cdt\.llm_attn/(while/body/closed_call/)?"
                         + scope + "/", text), scope
    for layer in ("llm_experts", "llm_router", "llm_head", "llm_norm"):
        assert f"cdt.{layer}/" in text, layer
    step = jax.jit(lambda c, t: Z.decode_step(CFG, params, c, t, T)).lower(
        Z.empty_cache(CFG, T + NEW), ids[T]).compile().as_text()
    for scope in ("llm_cca_mix", "llm_cca_core"):
        assert f"cdt.llm_attn/{scope}/" in step, scope


# --- the benchmark's files --------------------------------------------------------


def _cell():
    import cdtbench.workload as workload

    return workload.assemble(CELL)


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "zaya1-8b.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "zaya" and preset.kind == "llm"
    cfg = dataclasses.asdict(preset.llm)
    for key, value in cfg.items():
        if key in ("dtype",):
            assert held["llm"]["dtype"] == value
        else:
            assert held[key] == value, key
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    # every published width and count unchanged; the depth alone is cut
    assert held["reduced"] == ["num_hidden_layers"]
    assert held["published"]["num_hidden_layers"] == 40
    assert held["num_hidden_layers"] == 10 and len(held["layer_types"]) == 40
    for key, value in (("hidden_size", 2048), ("num_attention_heads", 8),
                       ("num_key_value_heads", 2), ("head_dim", 128),
                       ("cca_time0", 2), ("cca_time1", 2),
                       ("partial_rotary_factor", 0.5), ("num_experts", 16),
                       ("num_experts_per_tok", 1),
                       ("moe_intermediate_size", 2048),
                       ("router_hidden_size", 256), ("vocab_size", 262272),
                       ("tie_word_embeddings", True),
                       ("max_position_embeddings", 131072)):
        assert held[key] == value, key
    assert held["rope_parameters"]["hybrid"]["rope_theta"] == 5000000 \
        == held["rope_theta"]
    assert held["llm"]["parameters"] == Z.param_count(preset.llm)
    assert held["llm"]["cache_bytes_at_131072_positions"] \
        == llm_model.cache_bytes(Z.MODEL, preset.llm, 131072)
    assert sum(held["llm"]["parameters_by_part"].values()) \
        + 9 * 207583506 == held["llm"]["parameters"]
    assert any("ASSUMED" in line for line in held["assumed"])
    assert any("no skip" in line for line in held["assumed"])


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_zaya_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_zaya_reference.py").read_bytes()
    assert repo == copy


def test_the_cell_assembles_with_a_brief_that_fills_the_context():
    import cdtbench.workload as workload
    from cdtbench.kinds.zaya import request_sizes

    cell = _cell()
    assert cell.preset == "zaya1-8b" and cell.chips == 1
    assert request_sizes(cell) == (130944, 128)
    assert sum(request_sizes(cell)) \
        == cell.config["max_position_embeddings"]
    assert (cell.steps, cell.cfg, cell.image_hw) == (8, 6.0, (1024, 1024))
    bench = cell.bench
    ours = [m for m in bench["per_layer"] if m["name"].startswith("zaya_")]
    assert {m["name"] for m in cell.metrics("per_layer")} \
        >= {m["name"] for m in ours}
    assert all(m["workloads"] == [CELL] and m["moves"] == "request_p50_s"
               for m in ours)
    assert bench["per_layer"][-len(ours):] == ours    # appended as one run
    assert len(bench["per_layer"]) <= 128             # the contract's room
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": "zaya1-8b", "traffic": "ctx128k-sdxl8",
        "chips": 1, "why": entry["why"]}
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].startswith("zaya_")}
    assert {m["layer"] for m in ours} <= layers      # no new layer name
    small = workload.assemble(CELL, rehearsal=True)
    assert small.preset == "zaya-tiny" and request_sizes(small) == (40, 16)
    # brief128k-sdxl8 with node 9's prompt 128 tokens shorter, and its what
    other = workload.assemble("trinity-large-preview.brief128k-sdxl8")
    mine, theirs = dict(cell.traffic), dict(other.traffic)
    assert mine.pop("what") != theirs.pop("what")
    assert mine.pop("overrides") == {"9": {"prompt_tokens": 130944,
                                           "new_tokens": 128}}
    assert theirs.pop("overrides")["9"]["prompt_tokens"] == 131072
    assert mine == theirs


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    from cdtbench.kinds import zaya

    config = _cell().config
    cfg = Z.ZayaConfig.zaya_share()
    assert zaya.attention_params(config) == 5_242_880
    assert zaya.conv_params(config) == 332_800
    assert zaya.router_params(config) == 660_752
    assert zaya.expert_params(config) == 12_582_912
    assert zaya.layer_parameters(config) == 207_583_506
    assert zaya.parameters(config) == Z.param_count(cfg) \
        == config["llm"]["parameters"]
    T_ = 130944
    pairs = cfg.attended_keys(T_, 128)[("cca", "prefill")]
    core = zaya.core_flops(config, pairs)
    # a layer's causal core: 2 · T² · 1024 = 35.1 TFLOP
    assert core / 10 == pytest.approx(2 * T_ * T_ * 1024, rel=1e-4)
    experts = zaya.expert_flops(config, 10 * T_)
    assert experts / 10 == pytest.approx(3.295e12, rel=1e-3)
    total = zaya.prefill_flops(config, T_, pairs, 10 * T_)
    assert 0.86 < core / total < 0.90                # the issue's ~88%
    assert total == pytest.approx(3.99e14, rel=1e-2)


def _snapshot(requests, seconds):
    cfg = Z.ZayaConfig.zaya_share()
    pairs = cfg.attended_keys(130944, 128)
    return {
        "cdt_llm_expert_slots_total": {"series": [
            {"labels": {"where": "held", "phase": phase},
             "value": requests * 10 * tokens}
            for phase, tokens in (("prefill", 130944), ("decode", 128))]},
        "cdt_llm_attn_keys_total": {"series": [
            {"labels": {"layers": "cca", "phase": phase},
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
    import cdtbench.workload as workload
    from cdtbench import device_layers, readers
    from cdtbench.kinds import zaya

    cell = _cell()
    report = {"layers": {}, "phases": {
        "llm_prefill": {"llm_experts": {"seconds": 0.4},
                        "llm_attn": {"seconds": 2.5}}}}
    monkeypatch.setattr(device_layers, "of_run",
                        lambda ctx: report if ctx.get("trace") else None)
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 5.0}] * 2,
           "opened": _snapshot(1, 1.0), "closed": _snapshot(3, 1.0 + 0.512),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 4.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 0.5, "count": 1},
                         "llm_prefill": {"seconds": 3.2, "count": 1}},
                     "op_seconds": {"gqa_causal_mha.1": 1.5,
                                    "gqa_causal_mha.2": 0.8,
                                    "expert_tiles_mlp.4": 0.3,
                                    "fusion.7": 1.0}}}
    config = cell.config
    assert readers.read("zaya_decode_ms_per_token", ctx) == pytest.approx(2.0)
    assert readers.read("zaya_prefill_ms", ctx) == pytest.approx(2560.0)
    pairs = Z.ZayaConfig.zaya_share().attended_keys(130944, 128)[
        ("cca", "prefill")]
    flops = zaya.prefill_flops(config, 130944, pairs, 10 * 130944)
    assert readers.read("zaya_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 3.2, rel=1e-6)
    assert readers.read("zaya_cca_core_mxu_pct", ctx) == pytest.approx(
        100 * zaya.core_flops(config, pairs) / 197e12 / 2.3, rel=1e-6)
    assert readers.read("zaya_experts_mxu_pct", ctx) == pytest.approx(
        100 * zaya.expert_flops(config, 10 * 130944) / 197e12 / 0.4,
        rel=1e-6)
    for name in ("zaya_prefill_mfu_pct", "zaya_cca_core_mxu_pct",
                 "zaya_experts_mxu_pct"):
        assert 0 < readers.read(name, ctx) < 100, name
    # no trace, a trace without the kernel (the lax form, or the parent), or
    # a program without the series: nothing, not zero, and never a raise
    for name in ("zaya_prefill_mfu_pct", "zaya_cca_core_mxu_pct",
                 "zaya_experts_mxu_pct"):
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    assert readers.read("zaya_cca_core_mxu_pct",
                        {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("zaya_decode_ms_per_token", "zaya_prefill_ms",
                 "zaya_prefill_mfu_pct", "zaya_cca_core_mxu_pct",
                 "zaya_experts_mxu_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of them
    other = workload.assemble("keye-vl-2.0-30b-a3b.brief64k-sdxl8")
    for name in ("zaya_decode_ms_per_token", "zaya_prefill_mfu_pct",
                 "zaya_cca_core_mxu_pct", "zaya_experts_mxu_pct"):
        assert readers.read(name, {**ctx, "cell": other}) is None, name


def test_the_parity_tool_rehearses_and_its_walk_is_the_reference(
        params, ids, full_logits, capsys):
    """The tool at the tiny preset on the CPU (the stated precision and one
    arm that must fail), and its prompt walk + tail against
    ``reference.forward`` on the same ids."""
    from cdtbench import parity_zaya as P

    reference = P.load_reference()
    walk = P.prompt_walk(reference, CFG, params, np.asarray(ids[:T]), 7)
    assert len(walk) == 3 and walk[0][0].shape == (T, 2, 8)
    assert walk[1][2].shape == (reference.REACH, CFG.hidden_size)
    positions = [T - 1, T, T + 3, T + NEW - 1]
    got = P.tail_logits(reference, CFG, params, walk, np.asarray(ids), T,
                        positions)
    assert close(got, full_logits[jnp.asarray(positions)], 1e-5)
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "zaya1-8b.parity.json").read_text())
    assert set(limits["limits"]) == {"best_decode_row_rel_l2",
                                     "median_row_rel_l2",
                                     "worst_row_rel_l2"}
    assert all(0 < v["limit"] < 1 and v["reason"]
               for v in limits["limits"].values())
    assert limits["why_three"]
    assert P.main(["--workload", CELL, "--rehearse", "--seeds", "3",
                   "--degrade", "none,no_temp"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert [x["degrade"] for x in lines] == ["none", "no_temp"]
    assert lines[0]["inside_tolerances"] and lines[1]["seeds_failed"] == 1


@pytest.mark.parametrize("arm", ["kv_fp8", "stream_bf16", "no_eda",
                                 "no_temp"])
def test_the_parity_tools_arms_change_what_the_program_computes(
        params, ids, full_logits, arm):
    from cdtbench import parity_zaya as P

    with P.lowered(CFG, arm):
        got = through_the_cache(CFG, P.lowered_weights(params, arm), ids)
    assert not close(got, full_logits, 10 * F32_TOL), arm
    assert close(through_the_cache(CFG, params, ids), full_logits)


def test_the_golden_names_a_request_and_holds_an_image():
    from cdtbench import golden

    spec = golden.spec_of(CELL)
    assert spec["request"]["seed"] > 0 and spec["request"]["prompt"]
    assert (golden.HERE / f"{CELL}.png").exists()
