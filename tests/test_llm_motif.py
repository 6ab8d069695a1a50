"""The second prompt rewriter (grouped differential latent attention over
window and full layers, a four-stream mHC residual, PolyNorm experts) at
the tiny float32 preset, against the plain reference on seeded weights:
each layer kind, both forms of the attention, the ring and the full cache,
the stream mixer, PolyNorm, the router, the chip's share of the experts,
the decode loop through the shared pipeline, the nodes, the shipped graph
and the benchmark's readers of the cell."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.models import llm_motif as M
from comfyui_distributed_tpu.models import llm_motif_reference as R
from comfyui_distributed_tpu.ops import expert_share, latent_attention

ROOT = Path(__file__).resolve().parent.parent
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = M.MotifConfig.tiny()
W = CFG.sliding_window
CELL = "motif-3-beta.reprompt1k-sdxl8"


@pytest.fixture(scope="module")
def params():
    return M.init_motif(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (24,), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def full_logits(params, ids):
    return R.forward(CFG, params, ids)[0]


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# --- the layers against the reference -----------------------------------------


def test_the_tiny_preset_has_every_kind_of_layer():
    assert [CFG.is_full(i) for i in range(5)] == [False, False, False, True,
                                                  False]
    assert CFG.moe_layers == [1, 2, 3, 4]
    assert CFG.router_experts > CFG.num_experts      # a share, not the layer
    assert CFG.signal_heads == 8 and CFG.mhc_expansion_rate == 4


def test_prefill_is_the_reference_at_every_position(params, ids, full_logits):
    logits, cache, held = M.prefill(CFG, params, ids, 32, all_logits=True)
    assert close(logits, full_logits)
    want_held = R.forward(CFG, params, ids)[1]
    assert held.tolist() == [int(h) for h in want_held[1:]]
    # a window layer's cache is a ring of W rows, a full layer's the buffer
    assert [c.shape[0] for c in cache["c"]] == [W, W, W, 32, W]


@pytest.mark.parametrize("layer", [0, 1, 3],
                         ids=["window+dense", "window+experts",
                              "full+experts"])
def test_each_layer_kind_against_the_reference(params, ids, layer):
    """One layer alone: a one-layer stack whose layer is ``layer`` of the
    seeded model, so that a fault shows in the kind that has it."""
    one = dataclasses.replace(
        CFG, num_hidden_layers=1,
        n_dense_first_layers=0 if CFG.is_moe(layer) else 1,
        sliding_window_period=1 if CFG.is_full(layer) else 4)
    alone = {**params, "layers": [params["layers"][layer]]}
    got = M.prefill(one, alone, ids, 32, all_logits=True)[0]
    assert close(got, R.forward(one, alone, ids)[0])


@pytest.mark.parametrize("n_prompt", [2, W, 9],
                         ids=["inside-the-window", "at-the-window",
                              "the-ring-already-wrapped"])
def test_prefill_then_decode_through_both_caches_is_the_full_forward(
        params, ids, full_logits, n_prompt):
    """Decode runs to position 23 with a window of 4: every start lies
    before, at or beyond the window, and the ring wraps five times."""
    logits, cache, _ = M.prefill(CFG, params, ids[:n_prompt], 24)
    assert close(logits, full_logits[n_prompt - 1])
    step = jax.jit(lambda c, t, p: M.decode_step(CFG, params, c, t, p))
    for pos in range(n_prompt, 24):
        logits, cache, _ = step(cache, ids[pos], pos)
        assert close(logits, full_logits[pos]), pos


def test_a_bfloat16_run_fails_the_float32_tolerance(params, ids, full_logits):
    low = dataclasses.replace(CFG, dtype="bfloat16")
    logits = M.prefill(low, params, ids, 24, all_logits=True)[0]
    assert not close(logits, full_logits)
    assert close(logits, full_logits, 0.2)


# --- grouped differential latent attention ------------------------------------


def _gdla_inputs(T=11, G=2, J=5, nope=8, rope=4, rank=16, v=8, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        q_nope=jax.random.normal(k[0], (T, G, J, nope)),
        q_rope=jax.random.normal(k[1], (T, G, J, rope)),
        c=jax.random.normal(k[2], (T, rank)),
        k_rope=jax.random.normal(k[3], (T, rope)),
        w_uk=jax.random.normal(k[4], (rank, G, nope)) / 4,
        w_uv=jax.random.normal(k[5], (rank, G, v)) / 4,
        lam=jax.nn.sigmoid(jax.random.normal(k[6], (T, G, J - 1))))


def _plain_heads(x, window):
    """Every head's softmax attention over its group's decompressed keys
    and values, no subtraction: [T,G,J,v]."""
    T = x["c"].shape[0]
    k_nope = jnp.einsum("tc,cgd->tgd", x["c"], x["w_uk"])
    v = jnp.einsum("tc,cgv->tgv", x["c"], x["w_uv"])
    s = (jnp.einsum("tgjd,sgd->gjts", x["q_nope"], k_nope)
         + jnp.einsum("tgjr,sr->gjts", x["q_rope"], x["k_rope"])) * 0.3
    t = jnp.arange(T)
    seen = t[:, None] >= t[None, :]
    if window:
        seen &= t[None, :] > t[:, None] - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("gjts,sgv->tgjv", p, v)


@pytest.mark.parametrize("window", [None, 4], ids=["full", "window"])
def test_lambda_zero_is_grouped_latent_attention_on_the_signal_heads(window):
    x = _gdla_inputs()
    zero = {**x, "lam": jnp.zeros_like(x["lam"])}
    got = latent_attention.gdla_naive(**zero, scale=0.3, dtype=jnp.float32,
                                      window=window, block=4)
    assert close(got, _plain_heads(x, window)[:, :, :4], 1e-5)
    # and with the gate: the group's noise head, times λ, comes off
    heads = _plain_heads(x, window)
    got = latent_attention.gdla_naive(**x, scale=0.3, dtype=jnp.float32,
                                      window=window, block=4)
    assert close(got, heads[:, :, :4] - x["lam"][..., None] * heads[:, :, 4:],
                 1e-5)


def test_a_noise_head_is_shared_by_exactly_its_groups_signal_heads():
    x = _gdla_inputs()
    base = latent_attention.gdla_naive(**x, scale=0.3, dtype=jnp.float32)
    moved = {**x, "q_nope": x["q_nope"].at[:, 1, 4].add(1.0)}  # group 1's
    out = latent_attention.gdla_naive(**moved, scale=0.3, dtype=jnp.float32)
    changed = np.abs(np.asarray(out - base)).max(axis=(0, 3)) > 1e-6
    assert changed.tolist() == [[False] * 4, [True] * 4]


@pytest.mark.parametrize("window", [None, 4], ids=["full", "ring"])
def test_absorbed_gdla_on_the_latent_is_the_naive_one(window):
    x = _gdla_inputs()
    T = x["c"].shape[0]
    naive = latent_attention.gdla_naive(**x, scale=0.3, dtype=jnp.float32,
                                        window=window)
    for t in (0, 3, 4, T - 1):
        if window:                  # slot p % W holds position p, p ≤ t
            rows = jnp.arange(max(0, t - window + 1), t + 1)
            c = jnp.zeros((window, 16)).at[rows % window].set(x["c"][rows])
            kr = jnp.zeros((window, 4)).at[rows % window].set(
                x["k_rope"][rows])
        else:
            c, kr = x["c"], x["k_rope"]
        step = latent_attention.gdla_absorbed_step(
            x["q_nope"][t], x["q_rope"][t], c, kr,
            jnp.arange(c.shape[0]) <= t, x["w_uk"], x["w_uv"], x["lam"][t],
            0.3, jnp.float32)
        assert close(step, naive[t], 1e-5), t


# --- the stream mixer ---------------------------------------------------------


def test_sinkhorn_gives_a_doubly_stochastic_matrix():
    m = jnp.exp(jax.random.normal(jax.random.key(3), (7, 4, 4)))
    out = np.asarray(M.sinkhorn(m, CFG.mhc_sinkhorn_iters))
    assert np.abs(out.sum(-1) - 1).max() < 1e-3
    assert np.abs(out.sum(-2) - 1).max() < 1e-3
    assert (out > 0).all()
    assert close(out, R.sinkhorn(m, CFG.mhc_sinkhorn_iters), 1e-6)


def test_the_mixers_coefficients_are_the_references(params, ids):
    X = jax.random.normal(jax.random.key(4), (6, 4, CFG.hidden_size))
    p = params["layers"][2]["ffn_hc"]
    pre, post, res = M.hc_coefficients(CFG, p, X)
    assert pre.shape == post.shape == (6, 4) and res.shape == (6, 4, 4)
    assert float(pre.min()) > 0 and float(post.max()) < 2
    assert np.abs(np.asarray(res).sum(-1) - 1).max() < 1e-3
    # a token's coefficients do not depend on its neighbours
    alone = M.hc_coefficients(CFG, p, X[2])
    assert close(alone[2], res[2], 1e-5)


def test_identity_mix_and_one_hot_gates_are_the_plain_prenorm_residual():
    D, n = CFG.hidden_size, 4
    big = 40.0
    bias = jnp.concatenate([
        jnp.asarray([big, -big, -big, -big]),          # H_pre  = e_0
        jnp.asarray([0.0, -big, -big, -big]),          # H_post = e_0 (2σ(0))
        (jnp.eye(n) - 1.0).reshape(-1) * big])         # H_res  = I
    p = {"gamma": jnp.ones((n * D,)), "phi": jnp.ones((n * D, 24)),
         "alpha": jnp.zeros((3,)), "bias": bias,
         "norm": jnp.linspace(0.5, 1.5, D)}
    X = jax.random.normal(jax.random.key(5), (3, n, D))

    def sublayer(x):
        return jnp.tanh(x) * 2.0, None

    out, _ = M.hyper_connect(CFG, p, X, sublayer)
    plain = X[:, 0] + sublayer(M.rms_norm(X[:, 0], p["norm"],
                                          CFG.rms_norm_eps))[0]
    assert close(out[:, 0], plain, 1e-5)
    assert close(out[:, 1:], X[:, 1:], 1e-5)
    clipped = M.hyper_connect(dataclasses.replace(CFG, hidden_clamp=0.5), p,
                              X, sublayer)[0]
    assert float(jnp.abs(clipped).max()) == 0.5


# --- PolyNorm and the router --------------------------------------------------


def test_polynorm_is_the_formula_with_its_clamp_and_scale():
    k = jax.random.split(jax.random.key(6), 3)
    x = jax.random.normal(k[0], (5, 32))
    w_gu = jax.random.normal(k[1], (32, 2 * 48)) / 6
    w_down = jax.random.normal(k[2], (48, 32)) / 7
    poly = jnp.asarray([0.2, -0.4, 0.7, 3.0])          # b beyond the clamp
    got = expert_share.gated_mlp(x, w_gu, w_down, jnp.float32,
                                 M.poly_norm_gate(CFG), poly)
    z, u = np.split(np.asarray(x @ w_gu, np.float64), 2, axis=-1)

    def n(a):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + CFG.rms_norm_eps)

    p = 0.2 * n(z ** 3) - 0.4 * n(z ** 2) + 0.7 * n(z) + 0.5
    want = (0.5 * p * u) @ np.asarray(w_down, np.float64)
    assert close(got, want, 1e-5)
    assert close(R.poly_mlp(CFG, {"w_gu": w_gu, "w_down": w_down,
                                  "poly": poly}, x), want, 1e-5)
    unclamped = expert_share.gated_mlp(
        x, w_gu, w_down, jnp.float32,
        M.poly_norm_gate(dataclasses.replace(CFG, polynorm_bias_clamp=9.0)),
        poly)
    assert not close(unclamped, want, 1e-2)


def test_the_router_takes_the_top_8_of_384_normalised_times_2():
    routing = M.MotifConfig.motif_share().routing
    assert (routing.experts, routing.per_token, routing.groups,
            routing.scaling) == (384, 8, 1, 2.0)
    k = jax.random.split(jax.random.key(7), 2)
    x = jax.random.normal(k[0], (9, 16))
    w_router = jax.random.normal(k[1], (16, 384))
    idx, w = expert_share.route(x, w_router, None, routing)
    s = np.asarray(jax.nn.sigmoid(x @ w_router), np.float64)
    for t in range(9):
        best = np.argsort(-s[t])[:8]
        assert sorted(idx[t].tolist()) == sorted(best.tolist())
        assert close(np.sort(np.asarray(w[t])),
                     np.sort(2.0 * s[t, best] / s[t, best].sum()), 1e-5)
    assert close(w.sum(-1), 2.0, 1e-5)


# --- the share ----------------------------------------------------------------


def test_the_parts_of_all_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips: every share routes over all 16, computes
    its own two; the shared expert is added once."""
    uncut = dataclasses.replace(CFG, num_experts=16, first_expert=0)
    m = M.init_motif(uncut, jax.random.key(8))["layers"][2]["moe"]
    m = {**m, "e_poly": m["e_poly"] + jax.random.normal(
        jax.random.key(9), m["e_poly"].shape) * 0.2}     # experts differ
    x = jax.random.normal(jax.random.key(10), (7, CFG.hidden_size))
    want, want_held = R.experts(uncut, m, x)
    idx, w = expert_share.route(x, m["w_router"], None, uncut.routing)
    act = M.poly_norm_gate(CFG)
    total = expert_share.gated_mlp(x, m["shared"]["w_gu"],
                                   m["shared"]["w_down"], jnp.float32, act,
                                   m["shared"]["poly"])
    held = 0
    for first in range(0, 16, 2):
        share = {k: m[k][first:first + 2]
                 for k in ("e_gu", "e_down", "e_poly")}
        dense = expert_share.held_part_dense(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32,
            act, share["e_poly"], expert_chunk=1)
        whole = expert_share.held_part_dense(
            x, idx, w, share["e_gu"], share["e_down"], first, jnp.float32,
            act, share["e_poly"])
        token = jnp.stack([expert_share.held_part_token(
            x[t], idx[t], w[t], share["e_gu"], share["e_down"], first,
            jnp.float32, act, share["e_poly"]) for t in range(7)])
        assert close(dense, token, 1e-5) and close(dense, whole, 1e-5)
        total = total + dense
        held += int(expert_share.held_slots(idx, first, 2).sum())
    assert close(total, want)
    assert held == int(want_held) == 7 * CFG.experts_top_k


def test_a_share_leaves_out_what_absent_experts_would_add(params, ids):
    other = dataclasses.replace(CFG, first_expert=8)
    a = M.prefill(CFG, params, ids, 24)[0]
    b = M.prefill(other, params, ids, 24)[0]
    assert not close(a, b)
    assert close(b, R.forward(other, params, ids)[0][-1])


def test_the_published_share_counts_what_the_issue_counted():
    cfg = M.MotifConfig.motif_share()
    assert M.param_count(cfg) == 3_928_445_474
    tree = M.init_motif(cfg, None, abstract=True)
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
    assert 7.31 < held / 2**30 < 7.33
    assert cfg.signal_heads == 64 and cfg.qk_nope_head_dim == 128
    assert [cfg.is_full(i) for i in range(5)] == [False, False, False, True,
                                                  False]
    # the two kinds of cache at this cell's 2048 positions: 16 x apart
    sizes = llm_model.cache_bytes(cfg.model, cfg, 2048)
    assert sizes == {"window": 4 * 128 * 576 * 2, "full": 2048 * 576 * 2}


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_binds_the_same_two_labelled_programs(params):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is M.MODEL
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
    assert out["held_prefill"].shape == out["held_decode"].shape == (4,)
    assert out["cache_bytes"] == {
        "window": 4 * W * 20 * 4, "full": 24 * 20 * 4}
    # the taps are the reference's logits on the drawn ids
    out = pipe.generate(list(range(16)), 256, seed=2, temperature=0.7)
    seq = jnp.concatenate([jnp.arange(16), jnp.asarray(out["ids"])])
    want = R.forward(CFG, params, seq)[0]
    assert close(out["tap_logits"][0], want[16 + 127])
    assert close(out["tap_logits"][1], want[16 + 255])


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models import llm_hybrid
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["motif-tiny"].kind == PRESETS["motif-3-beta"].kind == "llm"
    assert PRESETS["motif-3-beta"].llm == M.MotifConfig.motif_share()
    assert PRESETS["motif-3-beta"].llm.model is M.MODEL
    assert PRESETS["ling-tiny"].llm.model is llm_hybrid.MODEL
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("motif-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("motif-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("motif-tiny") is bundle
    from comfyui_distributed_tpu.cluster.residency import bundle_bytes

    assert bundle_bytes(bundle) == 4 * M.param_count(CFG)


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "motif-tiny"
    graph["9"]["inputs"].update(prompt_tokens=16, new_tokens=8)
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

    def slots(phase):
        return sum(tm.LLM_EXPERT_SLOTS.labels(where=k, phase=phase).value
                   for k in ("held", "absent"))

    def mixes(phase):
        return tm.LLM_STREAM_MIX.labels(phase=phase).value

    before = {phase: (slots(phase), mixes(phase))
              for phase in ("prefill", "decode")}
    texts = [executor.execute(_shipped_graph(tmp_path, seed))["9"][0]
             for seed in (11, 11, 12)]
    assert texts[0] == texts[1] != texts[2]
    assert len(texts[0].split()) == 8
    assert all(w[0] == "t" and 0 <= int(w[1:]) < CFG.vocab_size
               for w in texts[0].split())
    assert len(list(tmp_path.glob("*.png"))) >= 1
    if telemetry.enabled():
        for phase, tokens in (("prefill", 16), ("decode", 8)):
            assert slots(phase) - before[phase][0] \
                == 3 * tokens * CFG.experts_top_k * 4
            assert mixes(phase) - before[phase][1] == 3 * tokens * 2 * 5
        assert tm.LLM_CACHE_BYTES.labels(layers="window").value \
            == 4 * W * 20 * 4
        assert tm.LLM_CACHE_BYTES.labels(layers="full").value == 24 * 20 * 4


def test_the_first_rewriter_mixes_no_streams_and_names_its_cache():
    from comfyui_distributed_tpu.models import llm_hybrid

    cfg = llm_hybrid.LLMConfig.tiny()
    assert cfg.stream_mixes_per_token == 0
    assert cfg.routed_slots_per_token == cfg.num_experts_per_tok * 6
    sizes = llm_model.cache_bytes(cfg.model, cfg, 24)
    assert set(sizes) == {"recurrent", "full"} and sizes["full"] \
        == 1 * 24 * (16 + 4) * 4


# --- the benchmark's files ----------------------------------------------------


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "motif-3-beta.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "motif" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    shared = [k for k in fields if k in held]
    assert len(shared) >= 27
    for key in shared:
        assert held[key] == fields[key], key
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == M.param_count(preset.llm)
    assert sum(n * (4 if "each of 4" in part else 1) for part, n in
               held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert "8 chips share each layer" in held["deployment"]
    assert held["router_experts"] == held["published"]["num_experts"] \
        == 8 * held["num_experts"]
    assert held["published"]["vocab_size"] == 8 * held["vocab_size"]
    graph = json.loads((ROOT / "cdtbench" / "workflows"
                        / "reprompt-sdxl.json").read_text())
    assert held["image_leg"] == graph["1"]["inputs"]["ckpt_name"] == "sdxl"
    assert graph == json.loads((ROOT / "workflows"
                                / "reprompt-sdxl.json").read_text())
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    assert set(held["reduced"]) == set(held["reduced_why"]) == {
        "num_hidden_layers", "n_dense_first_layers", "num_experts",
        "vocab_size", "num_nextn_predict_layers"}
    # every number of the catalog's config, under its key, but the reduced
    catalog = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Motif-3-Beta"' in line) if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").is_file() \
        else None
    if catalog:
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in held["reduced"]:
                assert held[key] == value, key


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_motif_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_motif_reference.py").read_bytes()
    assert repo == copy


def _cell():
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import workload

    return workload.assemble(CELL)


def test_the_cell_assembles_with_the_rewriters_sizes_and_the_units_step():
    from cdtbench.kinds.motif import request_sizes

    cell = _cell()
    assert cell.preset == "motif-3-beta" and cell.chips == 1
    assert request_sizes(cell) == (1024, 1024)
    assert (cell.steps, cell.cfg, cell.step_key) == (8, 6.0, "1024x1024.b2")
    assert cell.config["serve_env"] == {}
    small = __import__("cdtbench.workload", fromlist=["assemble"]).assemble(
        CELL, rehearsal=True)
    assert small.preset == "motif-tiny"
    assert small.graph["1"]["inputs"]["ckpt_name"] == "tiny"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"motif_decode_ms_per_token", "motif_prefill_ms",
                     "motif_decode_hbm_pct", "motif_held_slot_pct",
                     "motif_window_cache_pct", "denoise_ms_per_step"}
    # Ling's own readers (keyed by kind) stay out; the four keyed by device
    # scope (PR 34) serve every rewriter
    scoped = {"llm_attn_pct", "llm_experts_pct", "llm_ffn_pct",
              "llm_head_sample_pct"}
    assert scoped <= names
    assert not {n for n in names - scoped if n.startswith("llm_")}


def test_decode_bytes_count_the_leaves_the_model_holds():
    """``decode_bytes_per_token`` is written from the configuration's
    sizes; the model's own weight tree must give the same bytes."""
    from cdtbench.kinds.motif import decode_bytes_per_token

    cell = _cell()
    cfg = M.MotifConfig.motif_share()
    tree = M.init_motif(cfg, None, abstract=True)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    fixed = expert = 0
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        size = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if "e_gu" in name or "e_down" in name or "e_poly" in name:
            expert += size // cfg.num_experts      # ONE expert of each layer
        elif "embed" in name:
            fixed += cfg.hidden_size * leaf.dtype.itemsize     # one row
        else:
            fixed += size
    row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 2
    cache = 4 * cfg.sliding_window * row + (1024 + 512) * row
    want = fixed + cache + 0.125 * cfg.experts_top_k * expert
    got = decode_bytes_per_token(cell.config, 0.125, 1024, 1024)
    assert abs(got - want) / want < 1e-6
    assert 1.70e9 < got < 1.74e9


def _snapshot(held, absent, seconds):
    def slots(where, phase, value):
        return {"labels": {"where": where, "phase": phase}, "value": value}

    return {
        "cdt_llm_expert_slots_total": {"series": [
            slots("held", "decode", held), slots("absent", "decode", absent),
            slots("held", "prefill", 3 * held),
            slots("absent", "prefill", absent)]},
        "cdt_llm_cache_bytes": {"series": [
            {"labels": {"layers": "window"}, "value": 589824.0},
            {"labels": {"layers": "full"}, "value": 2359296.0}]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": seconds / 10,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_device_time_decode_slots_and_the_gauge():
    from cdtbench import readers
    from cdtbench.kinds.motif import decode_bytes_per_token

    cell = _cell()
    slots = 3 * 1024 * 32
    ctx = {"cell": cell, "requests": 3, "records": [],
           "opened": _snapshot(100, 900, 1.0),
           "closed": _snapshot(100 + slots // 8, 900 + slots - slots // 8,
                               1.0 + 3 * 3.072),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"phase_seconds": {
               "llm_decode": {"seconds": 2.8672, "count": 1},
               "llm_prefill": {"seconds": 0.1, "count": 1}}}}
    assert readers.read("motif_decode_ms_per_token", ctx) \
        == pytest.approx(3.0)
    assert readers.read("motif_prefill_ms", ctx) == pytest.approx(307.2)
    need = decode_bytes_per_token(cell.config, 1 / 8, 1024, 1024)
    assert readers.read("motif_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / 2.8e-3, rel=1e-6)
    assert readers.read("motif_window_cache_pct", ctx) == pytest.approx(20.0)
    held = slots // 8 + 3 * (slots // 8)             # decode's and prefill's
    assert readers.read("motif_held_slot_pct", ctx) == pytest.approx(
        100 * held / (held + 2 * (slots - slots // 8)))
    # no trace, or a program that has no such series (the parent): nothing
    assert readers.read("motif_decode_hbm_pct", {**ctx, "trace": None}) \
        is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("motif_decode_hbm_pct", "motif_decode_ms_per_token",
                 "motif_prefill_ms", "motif_held_slot_pct",
                 "motif_window_cache_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name


def test_the_parity_tools_lower_arms_change_what_the_program_computes(
        params):
    """The two arms that must fail on the chip are built around the served
    code: here they only have to move the logits, and leave no trace."""
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_motif

    ids16 = list(range(16))
    sound = pipeline_llm.LLMPipeline(CFG, params).generate(ids16, 8, 1, 0.7)
    with parity_motif.streams_in_bfloat16():
        low = pipeline_llm.LLMPipeline(CFG, params).generate(ids16, 8, 1,
                                                             0.7)
    assert not close(low["prefill_logits"], sound["prefill_logits"], 1e-4)
    again = pipeline_llm.LLMPipeline(CFG, params).generate(ids16, 8, 1, 0.7)
    assert np.array_equal(np.asarray(again["prefill_logits"]),
                          np.asarray(sound["prefill_logits"]))
    fp8 = parity_motif.experts_in_fp8(params)
    assert fp8["layers"][1]["moe"]["e_gu"].dtype == jnp.float8_e4m3fn
    assert fp8["layers"][0] is params["layers"][0]
    low = pipeline_llm.LLMPipeline(CFG, fp8).generate(ids16, 8, 1, 0.7)
    assert not close(low["prefill_logits"], sound["prefill_logits"], 1e-4)
    assert close(parity_motif.load_reference().forward(
        CFG, params, jnp.arange(16))[0], R.forward(
        CFG, params, jnp.arange(16))[0], 1e-6)


def test_the_shared_modules_leave_this_models_programs_as_they_were():
    """What PR 32 added to the shared code does not reach this model: its
    prefill takes the prompt whole, the one rule picks the dense-masked
    experts at its cell's 1024 prompt rows (48 held of 384: 21 rows an
    expert, under half a tile), and ``rope_interleaved`` without a table
    is the θ formula bit for bit."""
    share = M.MotifConfig.motif_share()
    assert share.model.prefill_chunk is None
    assert (share.num_experts, share.router_experts) == (48, 384)
    assert expert_share.prefill_form(1024, share.routing) == "dense"
    assert pipeline_llm.LLMPipeline(share, None).prefill_plan(1024) \
        == (1024, 1, "dense")
    # the dense arm of the rule is held_part_dense itself, chunks and all
    x = jax.random.normal(jax.random.key(3), (6, CFG.hidden_size))
    m = M.init_motif(CFG, jax.random.key(4))["layers"][2]["moe"]
    idx, w = expert_share.route(x, m["w_router"], None, CFG.routing)
    act = M.poly_norm_gate(CFG)
    direct = expert_share.held_part_dense(
        x, idx, w, m["e_gu"], m["e_down"], 0, jnp.float32, act, m["e_poly"],
        expert_chunk=4)
    ruled, rows = expert_share.held_part(
        x, idx, w, m["e_gu"], m["e_down"], 0, jnp.float32, CFG.routing, act,
        m["e_poly"], expert_chunk=4)
    assert np.array_equal(np.asarray(direct), np.asarray(ruled))
    assert int(rows) == 6 * CFG.num_experts
    x = jax.random.normal(jax.random.key(2), (4, 64))
    pos = jnp.asarray([0, 127, 1024, 2047])
    freq = share.rope_theta ** (-jnp.arange(0, 64, 2,
                                            dtype=jnp.float32) / 64)
    ang = pos.astype(jnp.float32)[:, None] * freq
    a, b = x[..., 0::2], x[..., 1::2]
    want = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)
    got = latent_attention.rope_interleaved(x, pos, share.rope_theta)
    assert np.array_equal(np.asarray(got), np.asarray(want))
