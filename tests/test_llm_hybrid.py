"""The hybrid language model (KDA + MLA + token-routed experts) at the tiny
float32 preset, against the plain reference on seeded weights: each block
kind, the two forms of each mechanism, the cache, the chip's share of the
experts and of the vocabulary, the decode loop as a SamplerProgram, the
nodes and the shipped graph."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.diffusion.samplers import (run_segment,
                                                        token_program)
from comfyui_distributed_tpu.models import llm_hybrid as L
from comfyui_distributed_tpu.models import llm_reference as R
from comfyui_distributed_tpu.ops import (delta_rule, expert_share,
                                         latent_attention)

ROOT = Path(__file__).resolve().parent.parent
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = L.LLMConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return L.init_llm(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (24,), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def full_logits(params, ids):
    return R.forward(CFG, params, ids)[0]


def take_experts(params, first: int, held: int):
    """The share ``[first, first+held)`` of every expert layer of an
    uncut model (router, shared expert and the rest untouched)."""
    layers = []
    for layer in params["layers"]:
        if "moe" in layer:
            moe = dict(layer["moe"])
            moe["e_gu"] = moe["e_gu"][first:first + held]
            moe["e_down"] = moe["e_down"][first:first + held]
            layer = {**layer, "moe": moe}
        layers.append(layer)
    return {**params, "layers": layers}


def take_vocab(params, first: int, rows: int):
    """Rows ``[first, first+rows)`` of the embedding and the head."""
    return {**params, "embed": params["embed"][first:first + rows],
            "head": params["head"][first:first + rows]}


def close(a, b, tol=F32_TOL):
    return float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max()) <= tol


# --- the ops, form against form ---------------------------------------------


def _kda_inputs(T=16, H=2, dk=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk)))
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dk))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, H, dk)) - 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    S0 = jax.random.normal(ks[5], (H, dk, dk))
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_chunked_delta_rule_is_the_recurrence(chunk):
    q, k, v, g, beta, S0 = _kda_inputs()
    S, outs = S0, []
    for t in range(q.shape[0]):
        S, o = delta_rule.kda_step(S, q[t], k[t], v[t], g[t], beta[t], 0.5)
        outs.append(o)
    o_chunked, S_chunked = delta_rule.kda_chunked(q, k, v, g, beta, S0, 0.5,
                                                  chunk)
    assert close(jnp.stack(outs), o_chunked, 1e-5)
    assert close(S, S_chunked, 1e-5)


def test_chunked_delta_rule_survives_the_gates_lower_bound():
    """64 tokens at the strongest decay: exp(-cumsum) alone overflows."""
    q, k, v, g, beta, S0 = _kda_inputs(T=64)
    g = jnp.full_like(g, -5.0)
    o, S = delta_rule.kda_chunked(q, k, v, g, beta, S0, 1.0, 64)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())


@pytest.mark.parametrize("form", ["stored", "absorbed"])
def test_absorbed_mla_is_the_naive_one(form):
    T, H, nope, r, rank, dv = 12, 2, 8, 4, 16, 8
    ks = jax.random.split(jax.random.key(3), 5)
    q_nope = jax.random.normal(ks[0], (T, H, nope))
    q_rope = jax.random.normal(ks[1], (T, H, r))
    c = jax.random.normal(ks[2], (T, rank))
    kr = jax.random.normal(ks[3], (T, r))
    w_b = jax.random.normal(ks[4], (rank, H * (nope + dv))) / 4.0
    naive = latent_attention.mla_naive(q_nope, q_rope, c, kr, w_b, 0.3,
                                       jnp.float32)
    pad = lambda x: jnp.concatenate([x, jnp.full((5, x.shape[1]), 9.0)])
    if form == "absorbed":          # what a token loop makes ahead of its steps
        w_b = latent_attention.absorbed_form(w_b, H)
    for t in (0, 5, T - 1):        # rows past t are masked, whatever they hold
        step = latent_attention.mla_absorbed_step(
            q_nope[t], q_rope[t], pad(c), pad(kr), t, w_b, 0.3, jnp.float32)
        assert close(step, naive[t], 1e-5)


def test_rope_rotates_interleaved_pairs_by_position():
    x = jnp.ones((3, 4))
    out = latent_attention.rope_interleaved(x, jnp.arange(3), 100.0)
    assert close(out[0], x[0], 1e-7)
    assert close(out[1, :2], [np.cos(1.0) - np.sin(1.0),
                              np.sin(1.0) + np.cos(1.0)], 1e-6)


# --- the router --------------------------------------------------------------


ROUTING = expert_share.Routing(experts=16, per_token=2, groups=4,
                               groups_kept=2, scaling=2.5)


def test_router_bias_moves_the_selection_and_not_the_weights():
    x = jnp.eye(4)[:1]
    w_router = jnp.zeros((4, 16)).at[0, 3].set(2.0).at[0, 2].set(1.0)
    idx, w = expert_share.route(x, w_router, jnp.zeros(16), ROUTING)
    assert sorted(idx[0].tolist()) == [2, 3]
    s = jax.nn.sigmoid(jnp.array([1.0, 2.0]))
    assert close(jnp.sort(w[0]), s / s.sum() * 2.5, 1e-6)
    # a bias pulls expert 9 (score sigmoid(0)) in; its WEIGHT is its bare score
    bias = jnp.zeros(16).at[9].set(5.0).at[8].set(5.0)
    idx, w = expert_share.route(x, w_router, bias, ROUTING)
    assert sorted(idx[0].tolist()) == [8, 9]
    assert close(w[0], jnp.array([1.25, 1.25]), 1e-6)
    assert close(w.sum(), 2.5, 1e-6)


def test_router_keeps_the_best_groups_by_their_top_two():
    """Group 0 holds the single best expert, but groups 1 and 2 have the
    better top-two sums: with 2 groups kept, nothing of group 0 is chosen."""
    logits = jnp.full((16,), -4.0)
    logits = logits.at[0].set(3.0)                       # group 0: one star
    logits = logits.at[4].set(2.0).at[5].set(2.0)        # group 1
    logits = logits.at[8].set(1.9).at[9].set(1.9)        # group 2
    x = jnp.ones((1, 1))
    idx, _ = expert_share.route(x, logits[None], jnp.zeros(16), ROUTING)
    assert sorted(idx[0].tolist()) == [4, 5]


# --- each block kind against the reference ----------------------------------


@pytest.mark.parametrize("layer", [0, 2, 5], ids=["kda+dense", "kda+experts",
                                                  "mla+experts"])
def test_each_block_kind_against_the_reference(params, layer):
    """One layer alone: the served prefill path of a one-layer model whose
    layer is layer ``layer`` of the tiny stack."""
    h = jax.random.normal(jax.random.key(7), (16, CFG.hidden_size))
    want, _ = R.layer_forward(CFG, layer, params["layers"][layer], h)
    # the same layer through the served code: a stack cut to that layer
    group = CFG.layer_group_size if CFG.is_mla(layer) else 99
    one = dataclasses.replace(CFG, num_hidden_layers=1,
                              layer_group_size=1 if CFG.is_mla(layer) else group,
                              first_k_dense_replace=0 if CFG.is_moe(layer)
                              else 1)
    stack = {"embed": h, "head": jnp.eye(CFG.hidden_size),
             "final_norm": jnp.ones(CFG.hidden_size),
             "layers": [params["layers"][layer]]}
    got, _, _ = L.prefill(one, stack, jnp.arange(16), 16, all_logits=True)
    # the head above is the identity after the final norm: undo the norm
    want_normed = L.rms_norm(want, jnp.ones(CFG.hidden_size),
                             CFG.rms_norm_eps)
    assert close(got, want_normed)


def test_prefill_is_the_reference_at_every_position(params, ids, full_logits):
    got, _, held = L.prefill(CFG, params, ids, 24, all_logits=True)
    assert close(got, full_logits)
    want_held = R.forward(CFG, params, ids)[1][CFG.first_k_dense_replace:]
    assert held.tolist() == [int(h) for h in want_held]


@pytest.mark.parametrize("n_prompt", [8, 16])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        params, ids, full_logits, n_prompt):
    logits, cache, _ = L.prefill(CFG, params, ids[:n_prompt], 24)
    assert close(logits, full_logits[n_prompt - 1])
    step = jax.jit(lambda c, tok, pos: L.decode_step(CFG, params, c, tok, pos))
    for pos in range(n_prompt, 24):
        logits, cache, _ = step(cache, ids[pos], jnp.int32(pos))
        assert close(logits, full_logits[pos]), pos


def test_a_bfloat16_run_fails_the_float32_tolerance(params, ids, full_logits):
    cfg16 = dataclasses.replace(CFG, dtype="bfloat16")
    got, _, _ = L.prefill(cfg16, params, ids, 24, all_logits=True)
    worst = float(jnp.abs(got - full_logits).max())
    assert worst > 10 * F32_TOL


# --- the chip's share ---------------------------------------------------------


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips of 8 experts each: their routed parts, plus the shared
    expert counted once, are the reference's uncut 32-expert layer."""
    uncut = dataclasses.replace(CFG, num_experts=CFG.router_experts)
    whole = L.init_llm(uncut, jax.random.key(2))
    m = whole["layers"][2]["moe"]
    x = jax.random.normal(jax.random.key(5), (12, CFG.hidden_size))
    want, _ = R.experts(uncut, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), m), x)
    idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                CFG.routing)
    total = expert_share.swiglu(x, m["shared"]["w_gu"], m["shared"]["w_down"],
                                jnp.float32)
    held = 0
    for first in range(0, CFG.router_experts, CFG.num_experts):
        share = take_experts(whole, first, CFG.num_experts)["layers"][2]["moe"]
        dense = expert_share.held_part_dense(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32)
        token = jnp.stack([expert_share.held_part_token(
            x[t], idx[t], w[t], share["e_gu"], share["e_down"], first,
            jnp.float32) for t in range(x.shape[0])])
        assert close(dense, token, 1e-5)
        total = total + dense
        held += int(expert_share.held_slots(idx, first,
                                            CFG.num_experts).sum())
    assert close(total, want)
    assert held == x.shape[0] * CFG.num_experts_per_tok


def test_a_share_leaves_out_what_absent_experts_would_add(params, ids):
    """The model with experts 8..15 differs from the one with 0..7: the
    share is computed, not ignored."""
    uncut = dataclasses.replace(CFG, num_experts=CFG.router_experts)
    whole = L.init_llm(uncut, jax.random.key(2))
    outs = []
    for first in (0, 8):
        cfg = dataclasses.replace(CFG, first_expert=first)
        outs.append(L.prefill(cfg, take_experts(whole, first, 8), ids,
                              24)[0])
        ref = R.forward(cfg, take_experts(whole, first, 8), ids, [23])[0]
        assert close(outs[-1], ref[0])
    assert not close(outs[0], outs[1], 1e-3)


def test_the_sliced_head_is_rows_of_the_uncut_head(ids):
    wide = dataclasses.replace(CFG, vocab_size=4 * CFG.vocab_size)
    whole = L.init_llm(wide, jax.random.key(4))
    first = CFG.vocab_size
    cut = take_vocab(whole, first, CFG.vocab_size)
    full, _, _ = L.prefill(wide, whole, ids + first, 24)
    got, _, _ = L.prefill(CFG, cut, ids, 24)
    assert got.shape == (CFG.vocab_size,)
    assert close(got, full[first:first + CFG.vocab_size], 1e-6)


def test_the_published_share_counts_what_the_issue_counted():
    cfg = L.LLMConfig.ling_flash_share()
    # the issue counted a 16-chip group's share, 32 experts a layer; the
    # cell holds a 32-chip group's, 16 a layer (PERF.md section 6, PR 26)
    assert L.param_count(dataclasses.replace(cfg, num_experts=32)) \
        == 1_771_220_320
    assert L.param_count(cfg) == 1_204_989_280
    assert len(cfg.kda_layers) == 7 and cfg.mla_layers == [5]
    assert cfg.moe_layers == [2, 3, 4, 5, 6, 7]
    abstract = L.init_llm(cfg, None, abstract=True)
    assert abstract["layers"][2]["moe"]["e_gu"].shape == (16, 2560, 1536)
    assert abstract["layers"][2]["moe"]["w_router"].shape == (2560, 512)
    assert abstract["head"].dtype == jnp.bfloat16


# --- the decode loop as a SamplerProgram --------------------------------------


def _program(params, logits, cache, n_prompt, n_steps, seed=0,
             temperature=0.7, tap_every=4):
    def forward(state, token, i):
        return L.decode_step(CFG, params, state, token, n_prompt + i)

    prog = token_program(forward, n_steps, jax.random.key(seed), temperature,
                         tap_every, len(CFG.moe_layers))
    return prog, prog.init((logits, cache))


def test_two_segments_of_run_segment_are_one_scan(params, ids):
    logits, cache, _ = L.prefill(CFG, params, ids[:8], 24)
    prog, carry = _program(params, logits, cache, 8, 12)
    whole = jax.jit(lambda c: run_segment(prog, c, 0, 12))(carry)
    first = jax.jit(lambda c: run_segment(prog, c, 0, 5))(carry)
    # through the host between the segments, as a preempted run would go
    first = jax.tree_util.tree_map(lambda a: jnp.asarray(np.asarray(a)),
                                   first)
    both = jax.jit(lambda c: run_segment(prog, c, 5, 7))(first)
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(both)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_decode_program_taps_the_logits_the_reference_gives(params, ids):
    logits, cache, _ = L.prefill(CFG, params, ids[:8], 24)
    prog, carry = _program(params, logits, cache, 8, 12)
    out_ids, _, _, taps, counts, finite = run_segment(prog, carry, 0, 12)
    assert bool(finite) and out_ids.shape == (12,)
    seq = jnp.concatenate([ids[:8], out_ids])
    want, held = R.forward(CFG, params, seq)
    for slot, step in enumerate((3, 7, 11)):
        assert close(taps[slot], want[8 + step])
    # the counts are the decode steps' share of the reference's count
    prefix = R.forward(CFG, params, ids[:8])[1]
    assert counts.tolist() == [int(a) - int(b) for a, b in zip(
        held[CFG.first_k_dense_replace:], prefix[CFG.first_k_dense_replace:])]


def test_temperature_zero_is_greedy_and_a_bad_logit_is_seen(params, ids):
    logits, cache, _ = L.prefill(CFG, params, ids[:8], 24)
    prog, carry = _program(params, logits, cache, 8, 3, temperature=0.0)
    out = run_segment(prog, carry, 0, 3)
    assert int(out[0][0]) == int(jnp.argmax(logits))
    prog, carry = _program(params, logits.at[3].set(jnp.nan), cache, 8, 3)
    assert not bool(run_segment(prog, carry, 0, 3)[5])


def test_the_pipeline_binds_two_labelled_programs_without_callbacks(params):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    prefill, decode = pipe.programs(16, 8)
    assert pipe.programs(16, 8)[1] is decode          # cached by sizes
    ids16 = jnp.arange(16) % CFG.vocab_size
    text = prefill.jitted.lower(prefill.weights, ids16).as_text()
    logits, cache, _ = prefill(ids16)
    text += decode.jitted.lower(decode.weights, logits, cache,
                                jax.random.key(0), jnp.float32(0.7)).as_text()
    assert "callback" not in text and "custom_call_target=\"xla_python" \
        not in text
    out = pipe.generate(list(range(16)), 8, seed=1, temperature=0.7)
    assert out["finite"] and out["ids"].shape == (8,)
    assert out["held_prefill"].shape == out["held_decode"].shape == (6,)


def test_generate_copies_any_sequence_of_ids_in_as_one_int32_array(params):
    """A list, an int64 array and an int32 array of one prompt are one
    request: the prefill program is handed an int32 array each time (no
    ``convert_element_type`` runs ahead of it) and answers the same."""
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    prefill, decode = pipe.programs(16, 8)
    seen = []

    def spy(ids):
        seen.append(ids)
        return prefill(ids)

    pipe.programs = lambda n_prompt, n_new: (spy, decode)
    prompt = [(7 * i + 3) % CFG.vocab_size for i in range(16)]
    outs = [pipe.generate(form, 8, seed=5, temperature=0.7)
            for form in (prompt, np.array(prompt, np.int64),
                         np.array(prompt, np.int32))]
    assert len(seen) == 3
    for ids in seen:
        assert isinstance(ids, jax.Array) and ids.dtype == jnp.int32
        assert ids.shape == (16,) and ids.tolist() == prompt
    for out in outs[1:]:
        assert out["rows_prefill"] == outs[0]["rows_prefill"] > 0
        for key in ("ids", "held_prefill", "held_decode", "prefill_logits"):
            assert np.array_equal(np.asarray(out[key]),
                                  np.asarray(outs[0][key])), key


# --- registry, nodes, the shipped graph ---------------------------------------


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["ling-tiny"].kind == "llm"
    assert PRESETS["ling-3.0-flash-vl"].kind == "llm"
    assert PRESETS["ling-3.0-flash-vl"].llm == L.LLMConfig.ling_flash_share()
    assert PRESETS["tiny"].kind == "unet" and PRESETS["sd3-tiny"].kind == "dit"
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("ling-tiny", model_registry=registry)
    with pytest.raises(ValidationError, match="CheckpointLoader"):
        LLMLoader().execute("tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("ling-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("ling-tiny") is bundle
    from comfyui_distributed_tpu.cluster.residency import bundle_bytes

    assert bundle_bytes(bundle) == 4 * L.param_count(CFG)


def test_rewrite_prompt_ids_pads_the_preamble_to_the_exact_length():
    from comfyui_distributed_tpu.graph.nodes_builtin import rewrite_prompt_ids

    short = rewrite_prompt_ids("a red fox", 512, 19648)
    long = rewrite_prompt_ids(" ".join(["word"] * 500), 512, 19648)
    assert len(short) == len(long) == 512
    assert short[:448] == long[:448]                  # the fixed preamble
    assert short[-3:] != long[-3:]
    assert all(2 <= t < 19648 for t in short + long)


def test_the_node_hands_generate_an_array_not_a_list(monkeypatch):
    """A long brief's ids reach ``generate`` as the int32 array they were
    built as: no list of the prompt's length stands between them."""
    from comfyui_distributed_tpu.graph.nodes_builtin import (
        LLMLoader, TPUPromptRewrite, rewrite_prompt_ids)

    (llm,) = LLMLoader().execute("ling-tiny")
    seen = []
    real = llm.pipeline.generate
    monkeypatch.setattr(
        llm.pipeline, "generate",
        lambda ids, *args: seen.append(ids) or real(ids, *args))
    (text,) = TPUPromptRewrite().execute(llm, "a red fox", 3,
                                         prompt_tokens=16, new_tokens=8)
    (ids,) = seen
    assert isinstance(ids, np.ndarray) and ids.dtype == np.int32
    assert ids.tolist() == rewrite_prompt_ids("a red fox", 16,
                                              CFG.vocab_size)
    assert len(text.split()) == 8


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sd3.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "sd3-tiny"
    graph["8"]["inputs"]["llm_name"] = "ling-tiny"
    graph["9"]["inputs"].update(prompt_tokens=16, new_tokens=8)
    graph["4"]["inputs"].update(width=16, height=16, steps=1)
    graph["3"]["inputs"]["seed"] = seed
    graph["6"]["inputs"]["output_dir"] = str(tmp_path)
    return graph


def test_the_shipped_graph_runs_and_the_seed_decides_the_text(tmp_path):
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.executor import (GraphExecutor,
                                                        validate_prompt)
    from comfyui_distributed_tpu.telemetry import metrics as tm

    assert not validate_prompt(_shipped_graph(tmp_path, 1))
    executor = GraphExecutor()
    def slots(phase):
        return sum(tm.LLM_EXPERT_SLOTS.labels(where=k, phase=phase).value
                   for k in ("held", "absent"))

    before = {phase: slots(phase) for phase in ("prefill", "decode")}
    texts = [executor.execute(_shipped_graph(tmp_path, seed))["9"][0]
             for seed in (11, 11, 12)]
    assert texts[0] == texts[1] != texts[2]
    assert len(texts[0].split()) == 8
    assert all(w[0] == "t" and 0 <= int(w[1:]) < CFG.vocab_size
               for w in texts[0].split())
    assert len(list(tmp_path.glob("*.png"))) >= 1
    if telemetry.enabled():
        per_token = CFG.num_experts_per_tok * len(CFG.moe_layers)
        assert slots("prefill") - before["prefill"] == 3 * 16 * per_token
        assert slots("decode") - before["decode"] == 3 * 8 * per_token


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "ling-3.0-flash-vl.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].kind == "llm"
    fields = dataclasses.asdict(preset.llm)
    shared = [k for k in fields if k in held]
    assert len(shared) >= 22
    for key in shared:
        assert held[key] == fields[key], key
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == L.param_count(preset.llm)
    assert "32 chips share each layer" in held["deployment"]
    assert held["router_experts"] == held["published"]["num_experts"] \
        == 32 * held["num_experts"]
    # the image leg is the preset sd3-medium.solo28 serves, not a second one
    graph = json.loads((ROOT / "cdtbench" / "workflows"
                        / "reprompt-sd3.json").read_text())
    assert held["image_leg"] == graph["1"]["inputs"]["ckpt_name"] \
        == "sd3-medium"
    assert set(held["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "vision_tower",
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"}
    catalog_widths = {"hidden_size": 2560, "intermediate_size": 6144,
                      "moe_intermediate_size": 768, "num_experts_per_tok": 8,
                      "num_attention_heads": 32, "kv_lora_rank": 512,
                      "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                      "v_head_dim": 128, "head_dim": 128, "n_group": 8,
                      "topk_group": 4}
    for key, value in catalog_widths.items():
        assert held[key] == value == fields[key], key


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_reference.py").read_text()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_hybrid_reference.py").read_text()
    assert repo == copy


# --- the benchmark's readers of the cell -------------------------------------


def _cell():
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import workload

    return workload.assemble("ling-3.0-flash-vl.reprompt1k")


def test_decode_bytes_count_the_leaves_the_model_holds():
    """``decode_bytes_per_token`` is written from the configuration's
    sizes; the model's own weight tree must give the same bytes."""
    from cdtbench.kinds.llm import decode_bytes_per_token, request_sizes

    cell = _cell()
    cfg = L.LLMConfig.ling_flash_share()
    n_prompt, n_new = request_sizes(cell)
    assert (n_prompt, n_new) == (512, 1024)
    tree = L.init_llm(cfg, None, abstract=True)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    fixed = expert = 0
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        size = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if "e_gu" in name or "e_down" in name:
            expert += size // cfg.num_experts      # ONE expert of each layer
        elif "embed" in name:
            fixed += cfg.hidden_size * leaf.dtype.itemsize     # one row
        else:
            fixed += size
    cache = L.empty_cache(cfg, n_prompt + n_new)
    state = sum(2 * s.size * 4 for s in cache["S"]) \
        + sum(2 * c.size * 2 for c in cache["conv"])
    latent = len(cfg.mla_layers) * (n_prompt + n_new / 2) \
        * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2
    share = 1 / 32
    want = fixed + state + latent \
        + share * cfg.num_experts_per_tok * expert
    got = decode_bytes_per_token(cell.config, share, n_prompt, n_new)
    assert abs(got - want) / want < 1e-3
    assert 1.15e9 < got < 1.25e9


def _snapshot(held, absent, seconds):
    def slots(where, phase, value):
        return {"labels": {"where": where, "phase": phase}, "value": value}

    return {
        "cdt_llm_expert_slots_total": {"series": [
            slots("held", "decode", held), slots("absent", "decode", absent),
            slots("held", "prefill", 7 * held),
            slots("absent", "prefill", absent)]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "flow_dp"}, "sum": 9.0, "count": 1}]}}


def test_the_roofline_share_reads_device_time_and_decode_slots():
    from cdtbench import readers
    from cdtbench.kinds.llm import decode_bytes_per_token

    cell = _cell()
    slots = 3 * 1024 * 48
    ctx = {"cell": cell, "requests": 3, "records": [],
           "opened": _snapshot(100, 900, 1.0),
           "closed": _snapshot(100 + slots // 32, 900 + slots - slots // 32,
                               1.0 + 3 * 2.048),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"phase_seconds": {
               "llm_decode": {"seconds": 1.8432, "count": 1},
               "llm_prefill": {"seconds": 0.03, "count": 1}}}}
    assert readers.read("llm_decode_ms_per_token", ctx) \
        == pytest.approx(2.0)
    # the device's 1.8 ms a token, not the host's 2.0; the decode steps'
    # held share (1/32), not the window's (prefill's is 7/32 here)
    need = decode_bytes_per_token(cell.config, 1 / 32, 512, 1024)
    assert readers.read("llm_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / 1.8e-3, rel=1e-6)
    # no trace, or a program that has no such counter (the parent): nothing
    assert readers.read("llm_decode_hbm_pct", {**ctx, "trace": None}) is None
    bare = {"cdt_pipeline_execute_seconds":
            ctx["opened"]["cdt_pipeline_execute_seconds"]}
    assert readers.read("llm_decode_hbm_pct",
                        {**ctx, "opened": bare, "closed": bare}) is None
    assert readers.read("llm_decode_ms_per_token",
                        {**ctx, "opened": bare, "closed": bare}) is None


def test_the_shared_modules_leave_this_models_programs_as_they_were():
    """What PR 32 added to the shared code does not reach this model: its
    prefill takes the prompt whole, the one rule picks the dense-masked
    experts at its cell's 512 prompt rows (16 held of 512: 8 rows an
    expert), and ``rope_interleaved`` without a table is the θ formula
    bit for bit."""
    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.ops import expert_share, latent_attention

    share = L.LLMConfig.ling_flash_share()
    assert share.model.prefill_chunk is None
    assert (share.num_experts, share.router_experts) == (16, 512)
    assert expert_share.prefill_form(512, share.routing) == "dense"
    assert LLMPipeline(share, None).prefill_plan(512) == (512, 1, "dense")
    whole = LLMPipeline(CFG, None).prefill_fn(16, 8).jitted
    out = jax.eval_shape(whole, L.init_llm(CFG, None, abstract=True),
                         jax.ShapeDtypeStruct((16,), jnp.int32))
    assert len(out) == 3          # no rows-multiplied output: not the scan
    x = jax.random.normal(jax.random.key(2), (5, 3, 64))
    pos = jnp.asarray([0, 7, 511, 1535, 40000])
    theta = share.rope_theta
    freq = theta ** (-jnp.arange(0, 64, 2, dtype=jnp.float32) / 64)
    ang = (pos.astype(jnp.float32)[:, None] * freq).reshape(5, 1, 32)
    a, b = x[..., 0::2], x[..., 1::2]
    want = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)
    got = latent_attention.rope_interleaved(x, pos, theta)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_rewrite_prompt_ids_cycles_the_preamble_hashed_once():
    """Same ids out as hashing every word of every request: the preamble's
    words are hashed once a vocabulary and cycled."""
    from comfyui_distributed_tpu.graph import nodes_builtin
    from comfyui_distributed_tpu.models.text import _stable_hash_token

    words = nodes_builtin.REWRITE_PREAMBLE.split()
    for text, n, vocab in (("a red fox jumps", 512, 19648),
                           ("x " * 9000, 32768, 20480),
                           ("one", 5, 64), ("", 40, 64)):
        user = [_stable_hash_token(w, vocab)
                for w in text.lower().split()][:max(1, n // 8)]
        want = [_stable_hash_token(words[i % len(words)], vocab)
                for i in range(n - len(user))] + user
        assert nodes_builtin.rewrite_prompt_ids(text, n, vocab) == want
    calls = []
    real = nodes_builtin._preamble_ids
    nodes_builtin._preamble_ids = lambda vocab: calls.append(vocab) or real(
        vocab)
    try:
        nodes_builtin.rewrite_prompt_ids("a b c", 32768, 20480)
    finally:
        nodes_builtin._preamble_ids = real
    assert calls == [20480]


@pytest.mark.parametrize("text,n,vocab", [
    ("a red fox jumps", 512, 19648), ("x " * 9000, 32768, 20480),
    ("one", 5, 64), ("", 40, 64), ("x", 131072, 151936)])
def test_rewrite_prompt_array_is_the_list_as_one_int32_array(text, n, vocab):
    from comfyui_distributed_tpu.graph import nodes_builtin
    from comfyui_distributed_tpu.models.text import _stable_hash_token

    ids = nodes_builtin.rewrite_prompt_array(text, n, vocab)
    assert isinstance(ids, np.ndarray) and ids.dtype == np.int32
    assert ids.shape == (n,) and ids.flags.c_contiguous
    assert ids.flags.writeable                 # the caller's own, not the cache
    # the list form's ids, and the ids every word hashed by itself gives
    lead = [_stable_hash_token(w, vocab)
            for w in nodes_builtin.REWRITE_PREAMBLE.split()]
    user = [_stable_hash_token(w, vocab)
            for w in text.lower().split()][:max(1, n // 8)]
    want = [lead[i % len(lead)] for i in range(n - len(user))] + user
    assert ids.tolist() == want \
        == nodes_builtin.rewrite_prompt_ids(text, n, vocab)


def test_the_preamble_is_hashed_once_a_vocabulary_and_kept_as_an_array():
    from comfyui_distributed_tpu.graph import nodes_builtin

    nodes_builtin._preamble_ids.cache_clear()
    for _ in range(3):
        nodes_builtin.rewrite_prompt_array("a b c", 4096, 20480)
    nodes_builtin.rewrite_prompt_ids("a b c", 4096, 19648)
    info = nodes_builtin._preamble_ids.cache_info()
    assert (info.misses, info.hits) == (2, 2)
    lead = nodes_builtin._preamble_ids(20480)
    assert lead.dtype == np.int32 and not lead.flags.writeable
    assert len(lead) == len(nodes_builtin.REWRITE_PREAMBLE.split())
