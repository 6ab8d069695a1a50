"""The eleventh prompt rewriter (power retention on every layer: a gated,
normalised linear recurrence over the degree-2 symmetric power of every key;
a cache of recurrent states and NO K/V row) at the tiny float32 preset,
against the plain reference on seeded weights — logits, not tokens. The
program carries a recurrence over ``φ(k)``, the reference evaluates the
quadratic form: the chunked prefill with a PADDED last chunk under a gate
that is data, decode through the states, every piece left out one at a time,
the grouping control, a cache that ``max_len`` does not size, the shared
pipeline, the nodes, the shipped graph, and the benchmark's files, counts and
readers of the cell."""

import dataclasses
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_brumby as B
from comfyui_distributed_tpu.models import llm_brumby_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import power_retention

ROOT = Path(__file__).resolve().parent.parent
# a float32 program against the float32 reference: logits of unit scale
# through 3 layers: 4e-7 measured; 1e-4 is far under what any left-out piece
# reads (the smallest, the head norms': 6e-2)
F32_TOL = 1e-4
CFG = B.BrumbyConfig.tiny()
CELL = "brumby-14b-base.ctx32k-sdxl8"
T, NEW = 37, 8          # 37 = 16 + 16 + 5: a padded last chunk


@pytest.fixture(scope="module")
def params():
    return B.init_brumby(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (T + NEW,), 0,
                              CFG.vocab_size)


@pytest.fixture(scope="module")
def full_logits(params, ids):
    return R.forward(CFG, params, ids)


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


def through_the_cache(cfg, params, ids, chunk=None, kernel=None):
    """Logits at every position: a chunked prefill of ``T`` tokens (every
    position's), then ``NEW`` decode steps."""
    logits, cache, _ = B.prefill(cfg, params, ids[:T], T + NEW,
                                 all_logits=True, chunk=chunk, kernel=kernel)
    rows = [logits]
    for t in range(T, T + NEW):
        step, cache, _ = B.decode_step(cfg, params, cache, ids[t], t)
        rows.append(step[None])
    return jnp.concatenate(rows)


# --- the model against the reference ------------------------------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 3
    assert T % CFG.prefill_chunk_tokens and T > 2 * CFG.prefill_chunk_tokens
    assert CFG.prefill_chunk_tokens > CFG.retention_block
    assert CFG.state_width == power_retention.width(CFG.head_dim) == 40
    assert CFG.model is B.MODEL and not CFG.moe_layers


@pytest.mark.parametrize("kernel, chunk", [("lax", 16), ("lax", 10),
                                           ("interpret", 16), ("lax", T)])
def test_prefill_and_decode_through_the_cache_are_the_reference(
        params, ids, full_logits, kernel, chunk):
    """The recurrence against the quadratic form, at every position: chunks
    that do not divide the prompt (16, 10), one chunk (37), and the Pallas
    kernel in the interpreter."""
    got = through_the_cache(CFG, params, ids, chunk=chunk, kernel=kernel)
    assert close(got, full_logits)


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_a_padded_last_chunk_leaves_the_states_as_the_whole_prefill_does(
        params, ids, kernel):
    """``S`` AND ``Z`` of every layer: a prompt walked in chunks of 16 (the
    last one 5 real rows and 11 padded) against one chunk of 37."""
    _, chunked, _ = B.prefill(CFG, params, ids[:T], T + NEW, chunk=16,
                              kernel=kernel)
    _, whole, _ = B.prefill(CFG, params, ids[:T], T + NEW, chunk=T,
                            kernel=kernel)
    assert sorted(chunked) == ["norm", "state"]
    for leaf in ("state", "norm"):
        for got, want in zip(chunked[leaf], whole[leaf]):
            assert close(got, want), leaf
            assert float(jnp.abs(want).max()) > 0


def test_padded_rows_gates_let_through_fail_the_decode_after_them(
        params, ids, full_logits, monkeypatch):
    """The recurrent-leaf contract under a data-dependent gate: a walk that
    takes the padded rows' KEYS out but lets their GATES through (what a
    mask by position alone does) decays the states, and the decode step
    after the padded chunk leaves the reference."""
    chunk = power_retention.retention_chunk

    def gates_through(S, Z, q, k, v, log_g, n_valid, dtype, block, **kw):
        keys_out = jnp.where((jnp.arange(k.shape[0]) < n_valid)[:, None, None],
                             k, 0.0)
        return chunk(S, Z, q, keys_out, v, log_g, k.shape[0], dtype, block,
                     **kw)

    def first_decode():
        _, cache, _ = B.prefill(CFG, params, ids[:T], T + NEW, chunk=16)
        return B.decode_step(CFG, params, cache, ids[T], T)[0]

    assert close(first_decode(), full_logits[T])
    monkeypatch.setattr(power_retention, "retention_chunk", gates_through)
    assert not close(first_decode(), full_logits[T], 100 * F32_TOL)


def test_the_reference_in_query_blocks_is_itself(params, ids, full_logits):
    assert close(R.forward(CFG, params, ids, block=7), full_logits, 1e-5)
    at = [3, T - 1, T + 2]
    assert close(R.forward(CFG, params, ids, positions=at),
                 full_logits[jnp.asarray(at)], 1e-6)


def test_a_bfloat16_run_is_within_its_stated_limit_and_over_float32s(
        params, ids, full_logits):
    """bfloat16 operands — ``φ``'s entries and the state as the read's
    operand among them: every position under 5e-2 of the reference's norm
    (1e-2 measured here; the chip's limits are
    ``brumby-14b-base.parity.json``'s), and the float32 tolerance refuses
    it."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    got = np.asarray(through_the_cache(cfg, params, ids), np.float64)
    want = np.asarray(full_logits, np.float64)
    rel = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    assert not close(got, want) and 1e-3 < np.median(rel) < 5e-2
    assert rel.max() < 0.1


# --- every piece, left out one at a time ----------------------------------------


def _weights_without(params, leaves):
    return {**params, "layers": [
        {**layer, "attn": {**layer["attn"], **{
            leaf: jnp.full_like(layer["attn"][leaf], value)
            for leaf, value in leaves.items()}}}
        for layer in params["layers"]]}


WEIGHTS_LEFT_OUT = {
    "the gate (γ ≡ 1)": {"w_gate": 0.0, "b_gate": 1e4},
    "the head norms' weights": {"q_norm": 1.0, "k_norm": 1.0}}


@pytest.mark.parametrize("piece", sorted(WEIGHTS_LEFT_OUT))
def test_a_parameter_at_its_usual_initialisation_moves_the_logits(
        params, ids, full_logits, piece):
    got = through_the_cache(
        CFG, _weights_without(params, WEIGHTS_LEFT_OUT[piece]), ids)
    assert not close(got, full_logits, 100 * F32_TOL), piece


def _walk_of(step):
    """A chunk's walk made of ``step`` a row (a plain program of
    ``retention_chunk``'s contract: a padded row leaves the state alone)."""
    def chunk(S, Z, q, k, v, log_g, n_valid, dtype, block, **kw):
        def body(carry, xs):
            *row, real = xs
            S, Z, o = step(*carry, *row)
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(real, new, old), (S, Z),
                carry), o
        real = jnp.arange(q.shape[0]) < n_valid
        (S, Z), o = jax.lax.scan(body, (S, Z), (q, k, v, log_g, real))
        return o, S, Z
    return chunk


def _numerator_alone(S, Z, q, k, v, log_g):
    """``z ≡ 1``: the step without its quotient."""
    S, Z, _ = _STEP(S, Z, q, k, v, log_g)
    G, d = k.shape
    num = jnp.einsum("gjD,gvD->gjv",
                     power_retention.phi(q.reshape(G, -1, d)), S)
    return S, Z, num.reshape(q.shape[0], -1)


def _degree_one(S, Z, q, k, v, log_g):
    """The square left out: ``φ`` the identity — a ``d_v × d`` state in the
    first ``d`` columns of ``S``, the normaliser ``Σ w k`` in ``Z``'s first
    row."""
    G, d = k.shape
    g = jnp.exp(log_g)[:, None]
    S = S.at[:, :, :d].set(g[..., None] * S[:, :, :d]
                           + v[:, :, None] * k[:, None])
    Z = Z.at[:, 0].set(g * Z[:, 0] + k)
    qg = q.reshape(G, -1, d)
    o = jnp.einsum("gjd,gvd->gjv", qg, S[:, :, :d]) \
        / jnp.einsum("gjd,gd->gj", qg, Z[:, 0])[..., None]
    return S, Z, o.reshape(q.shape[0], -1)


def _wrong_group(fn):
    """The gate of ANOTHER K/V head."""
    def wrong(S, Z, q, k, v, log_g, *a, **kw):
        return fn(S, Z, q, k, v, jnp.roll(log_g, 1, axis=-1), *a, **kw)
    return wrong


_STEP, _CHUNK = power_retention.retention_step, \
    power_retention.retention_chunk
# piece -> (retention_chunk, retention_step) as the program then has them
STEPS_LEFT_OUT = {
    "the normaliser (z ≡ 1)": (_walk_of(_numerator_alone), _numerator_alone),
    "the square (degree 1)": (_walk_of(_degree_one), _degree_one),
    "the gate taken from the wrong group": (_wrong_group(_CHUNK),
                                            _wrong_group(_STEP))}


@pytest.mark.parametrize("piece", sorted(STEPS_LEFT_OUT))
def test_a_piece_of_the_function_left_out_moves_the_logits(
        params, ids, full_logits, monkeypatch, piece):
    chunk, step = STEPS_LEFT_OUT[piece]
    monkeypatch.setattr(power_retention, "retention_chunk", chunk)
    monkeypatch.setattr(power_retention, "retention_step", step)
    got = through_the_cache(CFG, params, ids)
    assert not close(got, full_logits, 100 * F32_TOL), piece


def test_the_root_two_on_phis_cross_terms_is_in_the_program(
        params, ids, full_logits, monkeypatch):
    d = CFG.head_dim
    monkeypatch.setattr(power_retention, "phi", lambda a: jnp.concatenate(
        [a * jnp.roll(a, r, -1) for r in range(d // 2 + 1)], -1))
    monkeypatch.setattr(power_retention, "retention_chunk", _walk_of(_STEP))
    got = through_the_cache(CFG, params, ids)
    assert not close(got, full_logits, 100 * F32_TOL)


def test_the_head_norms_are_in_the_program(params, ids, full_logits,
                                           monkeypatch):
    """The per-head RMS norm itself (its weights' arm is above): a query's
    scale cancels in the quotient, a KEY's does not."""
    normed = B.rms_norm

    def per_head_left_out(x, weight, eps):
        if x.ndim == 3 and x.shape[-1] == CFG.head_dim:
            return x.astype(jnp.float32) * weight
        return normed(x, weight, eps)
    monkeypatch.setattr(B, "rms_norm", per_head_left_out)
    got = through_the_cache(CFG, params, ids)
    assert not close(got, full_logits, 100 * F32_TOL)


def test_rope_is_in_the_program(params, ids, full_logits, monkeypatch):
    monkeypatch.setattr(B, "_rope", lambda x, cos, sin: x)
    got = through_the_cache(CFG, params, ids)
    assert not close(got, full_logits, 100 * F32_TOL)


def test_the_step_walk_the_left_out_pieces_are_built_on_is_the_program(
        params, ids, full_logits, monkeypatch):
    """The control of the arms above that replace the chunk's walk: the same
    walk with nothing left out is the reference."""
    monkeypatch.setattr(power_retention, "retention_chunk", _walk_of(_STEP))
    assert close(through_the_cache(CFG, params, ids), full_logits)


def test_a_state_a_query_head_from_its_groups_keys_is_the_same_function(
        params, ids, full_logits):
    """The grouping's control: every query head given a state of its OWN,
    folded from its group's ``k``, ``v`` and gate (6 K/V heads, one query
    head each), answers what the grouped program does."""
    J = CFG.num_attention_heads // CFG.num_key_value_heads
    own = dataclasses.replace(CFG, num_key_value_heads=CFG.num_attention_heads)
    H, G, d = CFG.num_attention_heads, CFG.num_key_value_heads, CFG.head_dim

    def spread(layer):
        w_in = layer["attn"]["w_in"]
        q, k, v = (w_in[:, :H * d], w_in[:, H * d:(H + G) * d],
                   w_in[:, (H + G) * d:])
        k, v = (jnp.repeat(a.reshape(-1, G, d), J, axis=1).reshape(-1, H * d)
                for a in (k, v))
        return {**layer, "attn": {
            **layer["attn"], "w_in": jnp.concatenate([q, k, v], axis=1),
            "w_gate": jnp.repeat(layer["attn"]["w_gate"], J, axis=1),
            "b_gate": jnp.repeat(layer["attn"]["b_gate"], J)}}

    wide = {**params, "layers": [spread(x) for x in params["layers"]]}
    assert close(through_the_cache(own, wide, ids), full_logits)


# --- a cache that max_len does not size ------------------------------------------


def test_the_cache_is_recurrent_leaves_only_and_max_len_sizes_nothing():
    small = jax.eval_shape(lambda: B.empty_cache(CFG, 1024))
    large = jax.eval_shape(lambda: B.empty_cache(CFG, 32768))
    assert jax.tree_util.tree_map(lambda a: a.shape, small) \
        == jax.tree_util.tree_map(lambda a: a.shape, large)
    assert sorted(small) == ["norm", "state"]
    G, d, n = CFG.num_key_value_heads, CFG.head_dim, CFG.num_hidden_layers
    want = {"state": n * G * (CFG.state_width * d + d * d) * 4}
    for max_len in (1, 1024, 32768):
        assert llm_model.cache_bytes(B.MODEL, CFG, max_len) == want


def test_the_served_cut_holds_the_same_bytes_at_every_length():
    cfg = B.BrumbyConfig.brumby_stage()
    held = llm_model.cache_bytes(B.MODEL, cfg, 1024)
    assert held == llm_model.cache_bytes(B.MODEL, cfg, 32768) \
        == {"state": 6 * 8 * (8320 * 128 + 128 * 128) * 4}
    assert 0.19 < held["state"] / 2**30 < 0.2


@pytest.mark.parametrize("name", ["llm_hybrid", "llm_motif", "llm_kimi",
                                  "llm_jamba", "llm_trinity", "llm_longcat",
                                  "llm_sala", "llm_glm", "llm_keye",
                                  "llm_zaya"])
def test_the_ten_modules_counts_are_what_they_were(name):
    """The ``attended_keys`` hook gained a model that attends to no key; the
    ten before it answer bit for bit what they did (pinned at PR 60's tree,
    64 prompt + 8 new tokens at each module's tiny preset)."""
    import importlib

    pinned = {
        "llm_trinity": {("full", "prefill"): 2080,
                        ("window", "prefill"): 1936, ("full", "decode"): 548,
                        ("window", "decode"): 256},
        "llm_sala": {("sparse", "prefill"): 3392,
                     ("lightning", "prefill"): 6240,
                     ("sparse", "decode"): 584,
                     ("lightning", "decode"): 1644},
        "llm_glm": {("sparse", "prefill"): 3510, ("sparse", "decode"): 480},
        "llm_keye": {("sparse", "prefill"): 1404, ("sparse", "decode"): 192},
        "llm_zaya": {("cca", "prefill"): 6240, ("cca", "decode"): 1644}}
    module = importlib.import_module(
        f"comfyui_distributed_tpu.models.{name}")
    config = next(v for k, v in vars(module).items()
                  if k.endswith("Config") and dataclasses.is_dataclass(v)
                  and v.__module__ == module.__name__)
    hook = getattr(config.tiny(), "attended_keys", None)
    if name not in pinned:
        assert hook is None
    else:
        assert hook(64, 8) == pinned[name]


def test_a_model_without_keys_reports_positions_folded():
    assert CFG.attended_keys(40, 8) == {
        ("retention", "prefill"): 3 * 40, ("retention", "decode"): 3 * 8}
    cfg = B.BrumbyConfig.brumby_stage()
    assert cfg.attended_keys(32640, 128)[("retention", "prefill")] \
        == 6 * 32640


# --- the weights ---------------------------------------------------------------


def test_the_published_cut_counts_what_the_issue_counted():
    cfg = B.BrumbyConfig.brumby_stage()
    assert B.param_count(cfg) == 6 * 330_352_904 + 2 * 777_912_320 + 5120
    tree = B.init_brumby(cfg, None, abstract=True)
    layer = tree["layers"][0]
    assert sum(a.size for a in jax.tree_util.tree_leaves(layer)) \
        == 330_352_904
    assert tree["rope"]["cos"].shape == (32768, 64)
    assert tree["head"].shape == tree["embed"].shape == (151936, 5120)
    assert cfg.state_width == 8320


def test_the_rope_table_is_the_fifth_rewriters(params):
    from comfyui_distributed_tpu.models import llm_trinity

    table = llm_trinity.rope_table(CFG)
    for k in ("cos", "sin"):
        assert np.array_equal(np.asarray(params["rope"][k]),
                              np.asarray(table[k]))
    cos, sin = R.rope_angles(CFG, CFG.max_position_embeddings)
    assert np.array_equal(np.asarray(cos), np.asarray(table["cos"]))


def test_seeded_weights_are_drawn_away_from_what_makes_the_mechanism_vanish(
        params):
    attn = [layer["attn"] for layer in params["layers"]]
    bias = np.concatenate([np.asarray(a["b_gate"]) for a in attn])
    assert 2.5 < bias.mean() < 5.5 and bias.std() > 0.3
    for a in attn:
        for leaf in ("q_norm", "k_norm"):
            w = np.asarray(a[leaf])
            assert 0.9 < w.mean() < 1.1 and 0.03 < w.std() < 0.3
        assert float(jnp.abs(a["w_gate"]).max()) < 1.0
    # the states remember: γ of the prompt's tokens lies well inside (0.8, 1)
    x = jax.random.normal(jax.random.key(2), (64, CFG.hidden_size))
    gamma = jax.nn.sigmoid(x @ attn[0]["w_gate"] + attn[0]["b_gate"])
    assert 0.8 < float(gamma.min()) and float(gamma.max()) < 0.9999


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_scans_the_continuation_inside_one_labelled_program(
        params, ids, full_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is B.MODEL
    assert pipe.prefill_plan(T) == (16, 3, None)
    prefill, decode = pipe.programs(T, 8)
    logits, cache, held, rows = prefill(ids[:T])
    assert close(logits, full_logits[T - 1])
    assert held.shape == rows.shape == (0,)
    out, taps, slots, finite = decode(logits, cache, jax.random.key(3),
                                      jnp.asarray(0.7, jnp.float32))
    assert out.shape == (8,) and bool(finite) and slots.shape == (0,)


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["brumby-tiny"].kind == PRESETS["brumby-14b-base"].kind \
        == "llm"
    assert PRESETS["brumby-14b-base"].llm == B.BrumbyConfig.brumby_stage()
    assert PRESETS["brumby-14b-base"].llm.model is B.MODEL
    assert PRESETS["brumby-tiny"].llm == CFG
    at = list(PRESETS).index("brumby-14b-base")
    assert list(PRESETS)[at:at + 2] == ["brumby-14b-base", "brumby-tiny"]
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("brumby-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("brumby-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("brumby-tiny") is bundle


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "brumby-tiny"
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
            "keys": {p: tm.LLM_ATTN_KEYS.labels(layers="retention",
                                                phase=p).value
                     for p in ("prefill", "decode")},
            "tokens": {p: tm.LLM_TOKENS.labels(phase=p).value
                       for p in ("prefill", "decode")},
            "slots": sum(tm.LLM_EXPERT_SLOTS.labels(where=k, phase=p).value
                         for k in ("held", "absent")
                         for p in ("prefill", "decode")),
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
            # positions folded: tokens × layers, linear in the tokens
            assert after["keys"][phase] - before["keys"][phase] \
                == 3 * tokens * layers
            assert after["tokens"][phase] - before["tokens"][phase] \
                == 3 * tokens
        assert after["slots"] == before["slots"]     # no expert layer
        assert after["chunks"] - before["chunks"] == 3 * 3
        assert tm.LLM_CACHE_POSITIONS.labels().value == 48
        # the first kind that does not grow with the positions
        assert tm.LLM_CACHE_BYTES.labels(layers="state").value \
            == llm_model.cache_bytes(B.MODEL, CFG, 48)["state"] \
            == llm_model.cache_bytes(B.MODEL, CFG, 48000)["state"]


def test_retention_is_told_apart_below_the_attention_scope(params, ids):
    """Every operation of steps 4–6 carries the plain named scope
    ``llm_retention`` BELOW ``cdt.llm_attn``, in both programs."""
    text = jax.jit(lambda i: B.prefill(CFG, params, i, T + NEW)).lower(
        ids[:T]).compile().as_text()
    assert re.search(r"cdt\.llm_attn/(while/body/closed_call/)?"
                     r"llm_retention/", text)
    for layer in ("llm_shared_ffn", "llm_head", "llm_norm"):
        assert f"cdt.{layer}/" in text, layer
    step = jax.jit(lambda c, t: B.decode_step(CFG, params, c, t, T)).lower(
        B.empty_cache(CFG, T + NEW), ids[T]).compile().as_text()
    assert "cdt.llm_attn/llm_retention/" in step


# --- the benchmark's files --------------------------------------------------------


def _cell():
    import cdtbench.workload as workload

    return workload.assemble(CELL)


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "brumby-14b-base.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "brumby" and preset.kind == "llm"
    for key, value in dataclasses.asdict(preset.llm).items():
        if key == "dtype":
            assert held["llm"]["dtype"] == value
        else:
            assert held[key] == value, key
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    # every published width and count unchanged; the depth alone is cut
    assert held["reduced"] == ["num_hidden_layers"]
    assert held["published"]["num_hidden_layers"] == 40
    assert held["num_hidden_layers"] == 6
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert held[key] == value, key
    assert held["source"] == "https://huggingface.co/manifestai/" \
        "Brumby-14B-Base/blob/main/config.json"
    assert held["llm"]["parameters"] == B.param_count(preset.llm)
    assert held["llm"]["cache_bytes"] \
        == llm_model.cache_bytes(B.MODEL, preset.llm, 32768) \
        == llm_model.cache_bytes(B.MODEL, preset.llm, 1024)
    assert held["llm"]["state_width_held"] == preset.llm.state_width
    assert sum(held["llm"]["parameters_by_part"].values()) \
        + 5 * 330352904 == held["llm"]["parameters"]
    tree = B.init_brumby(preset.llm, None, abstract=True)
    assert held["llm"]["bytes"] + held["llm"]["rope_table_bytes"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assumed = [line for line in held["assumed"] if "ASSUMED" in line]
    assert len(assumed) == 6
    for words in ("degree", "Per-head norm", "Rope", "Gate", "epsilon",
                  "output gate"):
        assert any(words in line for line in assumed), words
    assert "7, 7, 7, 7, 6, 6" in held["deployment"]


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_brumby_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_brumby_reference.py").read_bytes()
    assert repo == copy


def test_the_cell_assembles_with_a_brief_that_fills_the_context():
    from cdtbench.kinds.brumby import request_sizes

    cell = _cell()
    assert cell.preset == "brumby-14b-base" and cell.chips == 1
    assert request_sizes(cell) == (32640, 128)
    assert sum(request_sizes(cell)) \
        == cell.config["max_position_embeddings"]
    chunk = cell.config["prefill_chunk_tokens"]
    assert divmod(32640, chunk) == (7, 3968)         # a padded last chunk
    bench = cell.bench
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": "brumby-14b-base", "traffic": "ctx32k-sdxl8",
        "chips": 1, "why": entry["why"]}
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "brumby-14b-base"]
    assert config["file"] == "cdtbench/configs/brumby-14b-base.json"
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    # per_layer was FULL when this cell came (128 of the contract's 128):
    # the cell lists no metric of its own; the seven ``brumby_*`` readers
    # (layer_metrics/, kinds/brumby.py) wait for a `benchmark` PR's room
    assert len(bench["per_layer"]) == 128
    assert not [m for m in bench["per_layer"]
                if m["name"].startswith("brumby_")]
    assert {m["name"] for m in cell.metrics("per_layer")} >= {
        "host_overhead_ms", "denoise_ms_per_step", "device_idle_pct"}
    mine = json.loads((ROOT / "cdtbench" / "traffic"
                       / "ctx32k-sdxl8.json").read_text())
    theirs = json.loads((ROOT / "cdtbench" / "traffic"
                         / "ctx128k-sdxl8.json").read_text())
    for traffic in (mine, theirs):
        traffic.pop("what")
    assert mine.pop("overrides") == {"9": {"prompt_tokens": 32640,
                                           "new_tokens": 128}}
    assert theirs.pop("overrides")["9"]["prompt_tokens"] == 130944
    assert mine == theirs


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    from cdtbench.kinds import brumby

    config = _cell().config
    cfg = B.BrumbyConfig.brumby_stage()
    assert brumby.layer_parameters(config) == 330_352_904
    assert brumby.parameters(config) == B.param_count(cfg) \
        == config["llm"]["parameters"]
    assert brumby.state_width(config) == 8256
    # a token a layer: 660.7 MFLOP of weight products, 107.5 − 5.3 of the
    # state form's two products (no pair inside a block is counted)
    assert 2 * brumby.matrix_params(config) == pytest.approx(660.7e6, rel=1e-4)
    assert brumby.retention_flops(config, 1) == pytest.approx(102.2e6,
                                                              rel=1e-3)
    positions = cfg.attended_keys(32640, 128)[("retention", "prefill")]
    assert brumby.retention_flops(config, positions) == pytest.approx(
        20.0e12, rel=1e-2)
    total = brumby.prefill_flops(config, 32640, positions)
    assert total == pytest.approx(149.4e12, rel=1e-2)
    assert 0.13 < brumby.retention_flops(config, positions) / total < 0.14
    # a decoded token: 5.93 GB, of which the states read and written 0.42
    assert brumby.decode_bytes_per_token(config) == pytest.approx(5.935e9,
                                                                  rel=1e-3)


def _snapshot(requests, seconds):
    cfg = B.BrumbyConfig.brumby_stage()
    folded = cfg.attended_keys(32640, 128)
    return {
        "cdt_llm_attn_keys_total": {"series": [
            {"labels": {"layers": "retention", "phase": phase},
             "value": requests * n} for (_, phase), n in folded.items()]},
        "cdt_llm_tokens_total": {"series": [
            {"labels": {"phase": phase}, "value": requests * tokens}
            for phase, tokens in (("prefill", 32640), ("decode", 128))]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 2 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock(
        monkeypatch):
    import cdtbench.workload as workload
    from cdtbench import readers
    from cdtbench.kinds import brumby

    cell = _cell()
    scope = {"llm_prefill": 0.3, "llm_decode": 0.1}
    monkeypatch.setattr(brumby, "retention_seconds",
                        lambda ctx: scope if ctx.get("trace") else None)
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 4.0}] * 2,
           "opened": _snapshot(1, 1.0), "closed": _snapshot(3, 1.0 + 2.56),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 3.5,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 1.1, "count": 1},
                         "llm_prefill": {"seconds": 1.3, "count": 1}},
                     "op_seconds": {"power_retention.1": 0.25,
                                    "fusion.7": 1.0}}}
    config = cell.config
    assert readers.read("brumby_decode_ms_per_token", ctx) \
        == pytest.approx(10.0)
    assert readers.read("brumby_prefill_ms", ctx) == pytest.approx(2560.0)
    assert readers.read("brumby_share_pct", ctx) == pytest.approx(
        100 * 3 * 2.56 / 8.0)
    flops = brumby.prefill_flops(config, 32640, 6 * 32640)
    assert readers.read("brumby_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 1.3, rel=1e-6)
    assert readers.read("brumby_decode_hbm_pct", ctx) == pytest.approx(
        100 * brumby.decode_bytes_per_token(config) / 819e9 / (1.1 / 128),
        rel=1e-6)
    assert readers.read("brumby_retention_pct", ctx) == pytest.approx(
        100 * 0.4 / 2.4)
    assert readers.read("brumby_retention_mxu_pct", ctx) == pytest.approx(
        100 * brumby.retention_flops(config, 6 * 32640) / 197e12 / 0.3,
        rel=1e-6)
    shares = ("brumby_prefill_mfu_pct", "brumby_decode_hbm_pct",
              "brumby_retention_pct", "brumby_retention_mxu_pct")
    for name in shares:
        assert 0 < readers.read(name, ctx) < 100, name
    # no trace, a trace without the scope (the parent), or a program without
    # the series: nothing, not zero, and never a raise
    for name in shares:
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    scope = None
    monkeypatch.setattr(brumby, "retention_seconds", lambda ctx: None)
    for name in ("brumby_retention_pct", "brumby_retention_mxu_pct"):
        assert readers.read(name, ctx) is None, name
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("brumby_decode_ms_per_token", "brumby_prefill_ms",
                 "brumby_share_pct", "brumby_prefill_mfu_pct",
                 "brumby_retention_mxu_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of them
    other = workload.assemble("zaya1-8b.ctx128k-sdxl8")
    for name in ("brumby_decode_ms_per_token", "brumby_share_pct") + shares:
        assert readers.read(name, {**ctx, "cell": other}) is None, name


def test_the_scopes_reader_finds_nothing_without_a_profile():
    from cdtbench.kinds import brumby

    ctx = {"cell": _cell(), "trace": {"busy_s": 1.0}}
    assert brumby.retention_seconds({**ctx, "trace": None}) is None


def test_the_parity_tool_rehearses_and_its_walk_is_the_reference(
        params, ids, full_logits, capsys):
    """The tool at the tiny preset on the CPU (the stated precision and one
    arm that must fail), and its prompt walk + tail against
    ``reference.forward`` on the same ids."""
    from cdtbench import parity_brumby as P

    reference = P.load_reference()
    walk = P.prompt_walk(reference, CFG, params, np.asarray(ids[:T]), 7)
    assert len(walk) == 3 and walk[0][0].shape == (T, 2, 8)
    assert walk[1][2].shape == (T, 2) and (np.diff(walk[1][2], axis=0)
                                           < 0).all()
    positions = [T - 1, T, T + 3, T + NEW - 1]
    got = P.tail_logits(reference, CFG, params, walk, np.asarray(ids), T,
                        positions)
    assert close(got, full_logits[jnp.asarray(positions)], 1e-5)
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "brumby-14b-base.parity.json").read_text())
    assert set(limits["limits"]) == {"best_decode_row_rel_l2",
                                     "median_row_rel_l2",
                                     "worst_row_rel_l2"}
    assert all(0 < v["limit"] < 1 and v["reason"]
               for v in limits["limits"].values())
    assert P.main(["--workload", CELL, "--rehearse", "--seeds", "3",
                   "--degrade", "none,no_gate"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert [x["degrade"] for x in lines] == ["none", "no_gate"]
    assert lines[0]["inside_tolerances"] and lines[1]["seeds_failed"] == 1


@pytest.mark.parametrize("arm", ["state_bf16", "stream_bf16", "no_gate"])
def test_the_parity_tools_arms_change_what_the_program_computes(
        params, ids, full_logits, arm):
    from cdtbench import parity_brumby as P

    with P.lowered(CFG, arm):
        got = through_the_cache(CFG, P.lowered_weights(params, arm), ids)
    assert not close(got, full_logits, 10 * F32_TOL), arm
    assert close(through_the_cache(CFG, params, ids), full_logits)


def test_the_golden_names_a_request_and_holds_an_image():
    from cdtbench import golden

    spec = golden.spec_of(CELL)
    assert spec["request"]["seed"] > 0 and spec["request"]["prompt"]
    assert (golden.HERE / f"{CELL}.png").exists()
