"""``ops/expert_share.py``'s router since it knows THREE places a slot can
fall (PR 44): a softmax, un-normalised, identity-aware ``route`` against a
float64 statement of it, with ties and with every slot on an identity
expert; the identity part and its count; ``held_slots`` and ``prefill_form``
reading an identity slot as neither held nor absent; and every older
rewriter's ``Routing``, ``route`` output and ``prefill_plan`` where they
were."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
from comfyui_distributed_tpu.models.registry import PRESETS
from comfyui_distributed_tpu.ops import expert_share
from comfyui_distributed_tpu.ops.expert_share import Routing

LONGCAT = Routing(512, 12, 1, 1, 6.0, score="softmax", normalised=False,
                  zero_experts=256)


def route64(x, w_router, bias, r: Routing):
    """``route`` as its docstring states it, in float64 numpy: scores,
    the selection on ``score + bias`` (the group step where the model has
    groups), the weights on the bare scores; ties go to the lower index."""
    logits = np.asarray(x, np.float64) @ np.asarray(w_router, np.float64)
    if r.score == "softmax":
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    else:
        s = 1.0 / (1.0 + np.exp(-logits))
    sel = s if bias is None else s + np.asarray(bias, np.float64)
    if r.groups > 1:
        T, per = sel.shape[0], sel.shape[1] // r.groups
        grouped = sel.reshape(T, r.groups, per)
        score = np.sort(grouped, -1)[..., -r.group_top:].sum(-1)
        kept = np.argsort(-score, -1, kind="stable")[:, :r.groups_kept]
        ok = np.zeros((T, r.groups), bool)
        np.put_along_axis(ok, kept, True, 1)
        sel = np.where(np.repeat(ok, per, 1), sel, -np.inf)
    idx = np.argsort(-sel, -1, kind="stable")[:, :r.per_token]
    w = np.take_along_axis(s, idx, 1)
    if r.normalised:
        w = w / w.sum(-1, keepdims=True)
    return idx, w * r.scaling


def _case(r: Routing, T=40, D=24, seed=0, bias_std=0.02):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (D, r.outputs)) / np.sqrt(D),
            jax.random.normal(ks[2], (r.outputs,)) * bias_std)


# --- the new router ------------------------------------------------------------


def test_routing_says_the_three_things_as_values_of_the_model():
    fields = {f.name: f.default for f in dataclasses.fields(Routing)}
    assert (fields["score"], fields["normalised"], fields["zero_experts"]) \
        == ("sigmoid", True, 0)
    assert LONGCAT.outputs == 768 and Routing(384, 8, 1, 1, 2.0).outputs == 384
    assert PRESETS["longcat-flash-omni"].llm.routing == LONGCAT
    unknown = dataclasses.replace(LONGCAT, score="tanh")
    with pytest.raises(KeyError):
        expert_share.route(*_case(unknown), unknown)


@pytest.mark.parametrize("bias_std", [0.0, 0.02, 1.0])
def test_a_softmax_unnormalised_router_is_its_float64_statement(bias_std):
    """768 outputs, top 12, times 6, not normalised; a bias of 1.0 swamps
    the scores (which are ~1/768), so the choice is the bias's alone and
    the weights still the bare scores."""
    x, w_router, bias = _case(LONGCAT, bias_std=bias_std)
    idx, w = expert_share.route(x, w_router, bias if bias_std else None,
                                LONGCAT)
    want_idx, want_w = route64(x, w_router, bias if bias_std else None,
                               LONGCAT)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    assert np.array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-5)
    # not normalised: a token's weights sum to 6 x the chosen scores' mass,
    # far below 6 (twelve of 768 outputs)
    assert float(w.sum(-1).max()) < 1.5
    assert (np.asarray(idx) >= 512).any() and (np.asarray(idx) < 512).any()
    if bias_std == 1.0:
        assert np.array_equal(
            np.sort(want_idx, -1),
            np.sort(np.tile(np.argsort(-np.asarray(bias, np.float64),
                                       kind="stable")[:12], (40, 1)), -1))


def test_ties_go_to_the_lower_index_and_a_token_may_take_only_identities():
    """A zero router: every score is 1/768 exactly, so the choice is the
    bias's, and equal biases tie. Rows 0–19 tie everywhere (outputs 0–11
    win); rows 20–39 see a bias that lifts twelve identity experts."""
    T, D = 40, 8
    x = jax.random.normal(jax.random.key(1), (T, D))
    w_router = jnp.zeros((D, 768))
    flat = jnp.zeros((768,))
    idx, w = expert_share.route(x, w_router, flat, LONGCAT)
    assert np.array_equal(np.asarray(idx), np.tile(np.arange(12), (T, 1)))
    np.testing.assert_allclose(np.asarray(w), 6.0 / 768, rtol=1e-6)
    lifted = flat.at[600:612].set(0.5).at[3].set(0.5 - 1e-3)
    idx, w = expert_share.route(x, w_router, lifted, LONGCAT)
    assert np.array_equal(np.asarray(idx),
                          np.tile(np.arange(600, 612), (T, 1)))
    want_idx, want_w = route64(x, w_router, lifted, LONGCAT)
    assert np.array_equal(np.asarray(idx), want_idx)
    mix, n_zero = expert_share.zero_part(x, idx, w, LONGCAT)
    assert int(n_zero) == T * 12                    # all twelve, every token
    np.testing.assert_allclose(np.asarray(mix), 12 * 6.0 / 768 * np.asarray(x),
                               rtol=1e-5)
    # and no form of the held part sees any of it
    e_gu = jax.random.normal(jax.random.key(2), (8, D, 16))
    e_down = jax.random.normal(jax.random.key(3), (8, 8, D))
    assert not np.asarray(expert_share.held_slots(idx, 0, 8)).any()
    dense = expert_share.held_part_dense(x, idx, w, e_gu, e_down, 0,
                                         jnp.float32)
    grouped, rows = expert_share.held_part_grouped(x, idx, w, e_gu, e_down,
                                                   0, jnp.float32, tile=4)
    token = expert_share.held_part_token(x[0], idx[0], w[0], e_gu, e_down,
                                         0, jnp.float32)
    assert not np.asarray(dense).any() and not np.asarray(grouped).any()
    assert not np.asarray(token).any() and int(rows) == 0


def test_the_identity_part_is_the_chosen_identity_weights_times_the_row():
    r = Routing(8, 3, 1, 1, 2.0, score="softmax", normalised=False,
                zero_experts=4)
    x = jax.random.normal(jax.random.key(4), (5, 6))
    idx = jnp.asarray([[0, 8, 11], [1, 2, 3], [9, 10, 11], [7, 8, 0],
                       [11, 0, 1]], jnp.int32)
    w = jax.random.uniform(jax.random.key(5), (5, 3)) + 0.1
    mix, n = expert_share.zero_part(x, idx, w, r)
    on_zero = np.asarray(idx) >= 8
    want = (np.where(on_zero, np.asarray(w), 0).sum(-1, keepdims=True)
            * np.asarray(x))
    np.testing.assert_allclose(np.asarray(mix), want, rtol=1e-6)
    assert int(n) == on_zero.sum() == 7
    assert not np.asarray(mix[1]).any()              # a token with none
    valid = jnp.arange(5) < 3                        # a padded chunk's rows
    assert int(expert_share.zero_part(x, idx, w, r, valid)[1]) \
        == on_zero[:3].sum() == 5
    # a router without identity experts has no such part
    plain = Routing(12, 3, 1, 1, 2.0)
    mix, n = expert_share.zero_part(x, idx, w, plain)
    assert int(n) == 0 and not np.asarray(mix).any()
    # held, absent and zero partition the slots
    held = np.asarray(expert_share.held_slots(idx, 0, 2))
    assert held.sum() + on_zero.sum() + ((np.asarray(idx) >= 2)
                                         & ~on_zero).sum() == 15
    assert not (held & on_zero).any()


def test_the_forms_rule_divides_by_the_routers_whole_width():
    """4096 rows x 12 / 768 outputs = 64: half a tile, the edge again
    (Trinity's 4096 x 4 / 256), so ``grouped``; divided by the 512 real
    experts alone it would be 96 and the edge at 2731 rows."""
    assert expert_share.prefill_form(4096, LONGCAT) == "grouped"
    assert expert_share.prefill_form(4095, LONGCAT) == "dense"
    real_only = dataclasses.replace(LONGCAT, zero_experts=0)
    assert expert_share.prefill_form(2731, real_only) == "grouped"
    assert expert_share.prefill_form(2731, LONGCAT) == "dense"
    pipe = LLMPipeline(PRESETS["longcat-flash-omni"].llm, None)
    assert pipe.prefill_plan(16384) == (4096, 4, "grouped")
    assert pipe.prefill_plan(1000) == (1000, 1, "dense")


# --- the five older rewriters: nothing moved ------------------------------------

OLDER = {
    "ling-3.0-flash-vl": (Routing(512, 8, 8, 4, 2.5), 512,
                          (512, 1, "dense")),
    "motif-3-beta": (Routing(384, 8, 1, 1, 2.0), 1024, (1024, 1, "dense")),
    "kimi-k2.6": (Routing(384, 8, 1, 1, 2.827), 32768,
                  (4096, 8, "grouped")),
    "trinity-large-preview": (Routing(256, 4, 1, 1, 2.448), 131072,
                              (4096, 32, "grouped")),
    "ling-tiny": (Routing(32, 4, 4, 2, 2.5), 24, (24, 1, "dense")),
    "motif-tiny": (Routing(16, 4, 1, 1, 2.0), 24, (24, 1, "dense")),
    "kimi-tiny": (Routing(16, 4, 1, 1, 2.827), 37, (16, 3, "grouped")),
    "trinity-tiny": (Routing(16, 2, 1, 1, 2.448), 21, (8, 3, "grouped")),
    # the sixth expert rewriter (PR 51) builds the same positional Routing
    "glm-5": (Routing(256, 8, 1, 1, 2.5), 65536, (4096, 16, "grouped")),
    "glm-tiny": (Routing(16, 4, 1, 1, 2.5), 40, (16, 3, "grouped")),
}


@pytest.mark.parametrize("preset", sorted(OLDER))
def test_an_older_rewriters_routing_route_and_plan_are_unmoved(preset):
    """Positional ``Routing(experts, per_token, groups, groups_kept,
    scaling)`` is what every older config builds: sigmoid, normalised, no
    identity expert. ``route`` is held to the float64 statement of what it
    did before PR 44 (sigmoid → [groups →] top-k of score + bias →
    normalise → scale)."""
    want, tokens, plan = OLDER[preset]
    cfg = PRESETS[preset].llm
    assert cfg.routing == want
    assert (want.score, want.normalised, want.zero_experts, want.outputs) \
        == ("sigmoid", True, 0, want.experts)
    assert LLMPipeline(cfg, None).prefill_plan(tokens) == plan
    x, w_router, bias = _case(want, seed=len(preset))
    for b in (bias, None):
        idx, w = expert_share.route(x, w_router, b, want)
        want_idx, want_w = route64(x, w_router, b, want)
        assert np.array_equal(np.asarray(idx), want_idx)
        np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(w).sum(-1), want.scaling,
                                   rtol=1e-5)


def test_a_model_without_an_expert_layer_still_needs_no_routing():
    cfg = PRESETS["ai21-jamba2-3b"].llm
    assert not cfg.moe_layers and not hasattr(cfg, "routing")
    assert LLMPipeline(cfg, None).prefill_plan(65536) == (4096, 16, None)
    from comfyui_distributed_tpu.diffusion import pipeline_llm

    assert pipeline_llm._slot_counts(cfg) == 0
    assert pipeline_llm._slot_counts(PRESETS["kimi-k2.6"].llm) == 4
    assert pipeline_llm._slot_counts(PRESETS["longcat-flash-omni"].llm) == 8
