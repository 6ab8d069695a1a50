"""The twelfth prompt rewriter (five window layers of a few keys with a
learned sink to one full layer, each kind with its own K/V head count and rope
base, keys wider than values, rope on a third of a head, a ring SHORTER than
the prefill chunk, routed experts with no shared one) at the tiny float32
preset, against the plain reference on seeded weights: a whole prefill through
both forms of the full layers' kernel, a prompt ending inside a chunk, decode
through ring and buffer far past the window, what a padded chunk owes a ring
shorter than itself, the shares adding up to the uncut layer, the shared
pipeline and nodes with the labelled counters, and the benchmark's files,
counts, readers and parity tool of the cell."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_mimo as M
from comfyui_distributed_tpu.models import llm_mimo_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import expert_share, gqa_sink_attention

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = M.MimoConfig.tiny()
CELL = "mimo-v2-flash.brief128k-sdxl8"
W, C = CFG.sliding_window, CFG.prefill_chunk_tokens
READERS = ["mimo_prefill_ms", "mimo_decode_ms_per_token", "mimo_share_pct",
           "mimo_prefill_mfu_pct", "mimo_decode_hbm_pct",
           "mimo_full_core_mxu_pct", "mimo_window_core_pct",
           "mimo_attn_core_pct", "mimo_window_cache_pct",
           "mimo_held_slot_pct"]


@pytest.fixture(scope="module")
def params():
    return M.init_mimo(CFG, jax.random.key(0))


@pytest.fixture(scope="module", autouse=True)
def _leave_no_programs_behind():
    """This file's compiled programs are dropped when it ends: in four of
    PR 64's seven whole runs the xdist worker that had run it lost its
    process to a segmentation fault inside jaxlib a file or two later
    (CHANGES.md, PR 64); nothing after this file needs them."""
    yield
    jax.clear_caches()


def ids_of(n, key=1):
    return jax.random.randint(jax.random.key(key), (n,), 0, CFG.vocab_size)


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


def through_the_cache(cfg, params, ids, T):
    """Logits at positions ``T−1 ..``: a chunked prefill of ``ids[:T]``, then
    one decode step a later id."""
    logits, cache, _ = M.prefill(cfg, params, ids[:T], len(ids))
    step = jax.jit(lambda c, t, p: M.decode_step(cfg, params, c, t, p))
    out = [logits]
    for i in range(T, len(ids)):
        logits, cache, _ = step(cache, ids[i], i)
        out.append(logits)
    return jnp.stack(out), cache


# --- prefill through rings and buffers, decode through both ------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.layer_types == M.MimoConfig.mimo_stage().layer_types
    assert [CFG.is_full(i) for i in range(7)] == [
        True, False, False, False, False, True, False]
    assert CFG.moe_layers == [1, 2, 3, 4, 5, 6] and not CFG.is_moe(0)
    assert (CFG.kv_heads(0), CFG.kv_heads(1)) == (2, 4)
    assert CFG.head_dim != CFG.v_head_dim and W < C and C % W == 0
    assert CFG.rotary_dim == 4 == CFG.head_dim // 3
    assert CFG.router_experts > CFG.n_routed_experts == CFG.num_experts
    stage = M.MimoConfig.mimo_stage()
    assert (stage.num_attention_heads, stage.num_key_value_heads,
            stage.swa_num_key_value_heads, stage.head_dim, stage.v_head_dim,
            stage.sliding_window, stage.rotary_dim) \
        == (64, 4, 8, 192, 128, 128, 64)
    assert stage.routing == expert_share.Routing(256, 8, 1, 1, 1.0)
    with pytest.raises(ValueError, match="a multiple of the window"):
        M.MimoConfig.tiny(prefill_chunk_tokens=6)


# a prompt shorter than the window, shorter than a chunk (a whole short
# chunk), exactly one chunk, whole chunks, and three that end INSIDE a chunk
# — one of them with fewer valid rows than the ring has slots
@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("T", [3, 5, 16, 48, 18, 21, 37])
def test_prefill_is_the_reference_at_every_position(params, T, kernel):
    ids = ids_of(T)
    want, held = R.forward(CFG, params, ids)
    got, _, counted = M.prefill(CFG, params, ids, T + 8, all_logits=True,
                                kernel=kernel)
    assert got.shape == (T, CFG.vocab_size) and close(got, want)
    assert [int(n) for n in counted] == [int(n) for n in held[1:]]


def test_the_reference_in_blocks_is_the_reference(params):
    ids = ids_of(21)
    whole, _ = R.forward(CFG, params, ids)
    for block in (4, 8, 16):
        blocked, _ = R.forward(CFG, params, ids, block=block)
        assert close(blocked, whole, 1e-6)
    some, _ = R.forward(CFG, params, ids, positions=[3, 20], block=8)
    assert close(some, whole[jnp.asarray([3, 20])], 1e-6)


@pytest.mark.parametrize("T", [3, 16, 21, 34])
def test_decode_runs_through_ring_and_buffer_far_past_the_window(params, T):
    """30 decoded tokens are more than seven windows: every ring slot is
    overwritten many times, and after a prompt that ends inside a chunk (21 =
    16 + 5, 34 = 32 + 2: fewer valid rows than the ring's four slots) the
    first token still finds the rows of the chunk before in their slots."""
    N = 30
    ids = ids_of(T + N, key=2)
    want, held = R.forward(CFG, params, ids)
    logits, cache, counted = M.prefill(CFG, params, ids[:T], T + N)
    assert close(logits, want[T - 1])
    step = jax.jit(lambda c, t, p: M.decode_step(CFG, params, c, t, p))
    total = np.asarray(counted)
    for i in range(N):
        logits, cache, n = step(cache, ids[T + i], T + i)
        assert close(logits, want[T + i]), i
        total = total + np.asarray(n)
    assert total.tolist() == [int(n) for n in held[1:]]


@pytest.mark.parametrize("T", [16, 18, 21, 37, 47])
def test_a_padded_chunk_leaves_the_ring_a_token_by_token_walk_leaves(params,
                                                                     T):
    """The contract ``chunked_prefill`` states for a ring SHORTER than the
    chunk: the last ``window`` of the chunk's ``n_valid`` rows at slots
    ``position % window`` — of a padded last chunk the rows before ``n_valid``,
    not its tail, and where fewer than a window are valid the ring keeps the
    rest. The walk: ``decode_step`` from an empty cache, a token at a time."""
    ids = ids_of(T, key=3)
    _, cache, _ = M.prefill(CFG, params, ids, T + W)
    walked = M.empty_cache(CFG, T + W)
    step = jax.jit(lambda c, t, p: M.decode_step(CFG, params, c, t, p))
    for i in range(T):
        _, walked, _ = step(walked, ids[i], i)
    for i in range(CFG.num_hidden_layers):
        for leaf in ("k", "v"):
            ours, theirs = np.asarray(cache[leaf][i]), \
                np.asarray(walked[leaf][i])
            if CFG.is_full(i):
                assert ours.shape[1] % CFG.attn_block_k == 0
                assert np.allclose(ours[:, :T], theirs[:, :T], atol=1e-5)
            else:
                assert ours.shape == (CFG.kv_heads(i), W, ours.shape[2])
                assert np.allclose(ours, theirs, atol=1e-5), (i, leaf)


def test_the_ring_after_a_chunk_is_the_newest_row_of_every_slot():
    """Slot ``j`` holds the newest position ``≡ j (mod window)`` not past the
    last valid one: a whole chunk's tail, a padded chunk's rows BEFORE
    ``n_valid``, and what the ring held where fewer than a window are valid
    (−1 here) or nothing was ever written."""
    ring = -jnp.ones((1, 4, 1))
    for start, n_valid, want in ((16, 16, [28, 29, 30, 31]),
                                 (16, 6, [20, 21, 18, 19]),
                                 (16, 2, [16, 17, -1, -1]),
                                 (0, 3, [0, 1, 2, -1])):
        rows = (start + jnp.arange(16.0)).reshape(1, 16, 1)
        got = M._ring_after(ring, rows, start, n_valid)[0, :, 0]
        assert got.tolist() == want, (start, n_valid)


def test_each_kind_of_layer_turns_by_its_own_table(params):
    ids = ids_of(21)
    assert not np.allclose(params["rope"]["full"]["cos"][5],
                           params["rope"]["window"]["cos"][5])
    assert params["rope"]["full"]["cos"].shape == (96, CFG.rotary_dim // 2)
    want, _, _ = M.prefill(CFG, params, ids, 21, all_logits=True)
    for kind in M.KINDS:
        still = {**params, "rope": {**params["rope"], kind: {
            "cos": jnp.ones_like(params["rope"][kind]["cos"]),
            "sin": jnp.zeros_like(params["rope"][kind]["sin"])}}}
        flat, _, _ = M.prefill(CFG, still, ids, 21, all_logits=True)
        assert not close(flat, want, 1e-3), kind
    # the angles are made in float64: position × frequency off the host
    stage = M.MimoConfig.mimo_stage()
    table = M.rope_table(M._rope_of(stage, "full"))
    angle = 131071 * 5e6 ** (-np.arange(32) / 32)
    assert np.allclose(np.asarray(table["cos"][131071]), np.cos(angle),
                       atol=1e-6)


def test_the_band_is_window_keys_the_querys_own_included(params):
    """Changing the id ``window`` positions back changes a window-only
    stack's last logits through no window layer: only through the full ones;
    with the full layers' attention zeroed it changes nothing, one nearer it
    does."""
    cfg = dataclasses.replace(CFG, num_hidden_layers=2, num_dense_layers=0,
                              layer_types=(M.SLIDING, M.SLIDING))
    p = M.init_mimo(cfg, jax.random.key(4))
    ids = ids_of(20, key=6)
    base, _, _ = M.prefill(cfg, p, ids, 20)
    # two layers of a band of W reach 2(W − 1) back
    out = ids.at[19 - 2 * (W - 1) - 1].add(1)
    moved, _, _ = M.prefill(cfg, p, out, 20)
    assert close(moved, base, 1e-6)
    inside = ids.at[19 - 2 * (W - 1)].add(1)
    moved, _, _ = M.prefill(cfg, p, inside, 20)
    assert not close(moved, base, 1e-5)


def test_a_bfloat16_run_fails_the_float32_tolerance(params):
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    ids = ids_of(21)
    want, _ = R.forward(CFG, params, ids)
    got, _, _ = M.prefill(cfg, params, ids, 21, all_logits=True)
    assert not close(got, want)
    # most logits within bfloat16's rounding (a flipped expert moves a row)
    assert float(np.median(np.abs(np.asarray(got - want)))) < 0.05


# --- what the kernel says of itself (the kernel, the band and the step alone:
# tests/test_gqa_attention.py) ---


def test_the_attention_line_names_both_widths(monkeypatch):
    from comfyui_distributed_tpu.ops import attention, flash_attention

    attention.reset_selections()
    q, k, v = (jax.random.normal(jax.random.key(7), shape)
               for shape in ((16, 8, 12), (2, 64, 12), (2, 64, 8)))
    gqa_sink_attention.causal_chunk(q, k, v, 0, 1.0, jnp.float32, 8, 16,
                                    kernel="interpret")
    assert attention.selection_summary() == ""        # the interpreter: none
    monkeypatch.setattr(flash_attention, "_platform", lambda: "tpu")
    attention.note_causal("gqa_causal", 64, 192, 4096, 133120, "bfloat16",
                          2048, 2048, 128, value_dim=128)
    assert attention.selection_summary().startswith(
        "h64.d192/128.q4096.kv")
    assert "gqa_causal:2048/2048/128" in attention.selection_summary()
    attention.reset_selections()
    attention.note_causal("gqa_causal", 48, 128, 4096, 133120, "bfloat16",
                          2048, 2048, 128)
    assert attention.selection_summary().startswith("h48.d128.q4096.kv")
    attention.reset_selections()


# --- the share of the experts ------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 4 of 16 experts each: their held parts summed — there
    is no shared expert; attention and the dense layer are outside the expert
    layer and counted ONCE — are the uncut reference's expert layer, and a
    whole block built on the sum is the uncut reference's block."""
    uncut = M.MimoConfig.tiny(n_routed_experts=16)
    params = M.init_mimo(uncut, jax.random.key(3))
    ids = ids_of(19, key=5)
    i, layer = 5, params["layers"][5]             # the full expert layer
    m = layer["moe"]
    x = jax.random.normal(jax.random.key(6), (19, uncut.hidden_size))
    want, slots = R.experts(uncut, R._f32(m), x)
    assert int(slots) == 19 * uncut.num_experts_per_tok
    idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                uncut.routing)
    total, held = 0.0, 0
    for first in range(0, 16, 4):
        part, _ = expert_share.held_part(
            x, idx, w, m["e_gu"][first:first + 4],
            m["e_down"][first:first + 4], first, jnp.float32, uncut.routing,
            tile=uncut.expert_tile)
        total = total + part
        held += int(expert_share.held_slots(idx, first, 4).sum())
    assert held == 19 * uncut.num_experts_per_tok
    assert close(total, want, 1e-5)
    # the whole block: the reference's layer on the uncut model, against
    # attention once + the four shares' expert layers summed
    h = R.embed(uncut, params, ids)
    cos, sin = R.rope_angles(uncut, 19)
    k, v = R.keys_values(uncut, False, layer, h, cos, sin)
    block, _ = R.layer_rows(uncut, False, True, layer, h, jnp.arange(19), k,
                            v, cos, sin)
    from comfyui_distributed_tpu.models.llm_hybrid import rms_norm
    q, kk, vv = M._attn_in(uncut, i, layer["attn"], rms_norm(
        h, layer["norm_in"], uncut.rms_norm_eps), (cos[:, 0], sin[:, 0]))
    o = gqa_sink_attention.causal_chunk(
        q, M._rows(kk, jnp.float32), M._rows(vv, jnp.float32), 0,
        uncut.head_dim ** -0.5, jnp.float32, 4, 4, kernel="lax")
    mid = h + M._attn_out(uncut, layer["attn"], o)
    served = mid
    for first in range(0, 16, 4):
        share = dataclasses.replace(uncut, n_routed_experts=4,
                                    first_expert=first)
        cut = {**layer, "moe": {**m, "e_gu": m["e_gu"][first:first + 4],
                                "e_down": m["e_down"][first:first + 4]}}
        out, n_held, _ = M._ffn(share, cut, i, mid, jnp.ones((19,), bool))
        served = served + (out - mid)
    assert close(served, block, 1e-5)


def test_the_stage_counts_what_the_issue_counted():
    cfg = M.MimoConfig.mimo_stage()
    assert M.param_count(cfg) == 3_429_955_392
    tree = M.init_mimo(cfg, None, abstract=True)
    assert tree["layers"][0]["attn"]["w_q"].shape == (4096, 12288)
    assert tree["layers"][0]["attn"]["w_kv"].shape == (4096, 4 * 320)
    assert tree["layers"][1]["attn"]["w_kv"].shape == (4096, 8 * 320)
    assert tree["layers"][1]["attn"]["w_o"].shape == (8192, 4096)
    assert tree["layers"][1]["attn"]["sink"].shape == (64,)
    assert "sink" not in tree["layers"][5]["attn"]
    assert tree["layers"][1]["moe"]["e_gu"].shape == (16, 4096, 4096)
    assert tree["layers"][1]["moe"]["w_router"].shape == (4096, 256)
    assert "shared" not in tree["layers"][1]["moe"]
    assert "ffn" in tree["layers"][0] and "moe" not in tree["layers"][0]
    assert tree["layers"][0]["ffn"]["w_gu"].shape == (4096, 32768)
    for kind in M.KINDS:
        assert tree["rope"][kind]["cos"].shape == (262144, 32)
    sizes = llm_model.cache_bytes(cfg.model, cfg, 131072 + 128)
    assert sizes["window"] == 5 * 8 * 128 * (192 + 128) * 2 == 3_276_800
    # 131 200 rows rounded up to the K block of 2048
    assert sizes["full"] == 2 * 4 * 133120 * (192 + 128) * 2
    share = 100 * sizes["window"] / (sizes["window"] + sizes["full"])
    assert share == pytest.approx(0.48, abs=0.01)


def test_attended_keys_are_the_masks_own_count():
    T, N = 21, 6
    pairs = CFG.attended_keys(T, N)
    row = np.arange(T + N)[:, None]
    col = np.arange(T + N)[None, :]
    causal = col <= row
    band = causal & (row - col < W)
    assert pairs["full", "prefill"] == 2 * causal[:T].sum()
    assert pairs["window", "prefill"] == 5 * band[:T].sum()
    assert pairs["full", "decode"] == 2 * causal[T:].sum()
    assert pairs["window", "decode"] == 5 * band[T:].sum()
    big = M.MimoConfig.mimo_stage().attended_keys(131072, 128)
    assert big["window", "prefill"] / 5 == pytest.approx(131072 * 128,
                                                         rel=1e-3)
    assert big["full", "prefill"] == 2 * (131072 * 131073 // 2)


# --- the shared pipeline, the nodes, the counters, the scopes ----------------


def test_the_pipeline_runs_it_like_the_other_eleven(params):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model.prefill_chunk is M.prefill_chunk
    assert pipe.prefill_plan(40)[:2] == (16, 3)
    prompt = [int(i) % CFG.vocab_size for i in range(3, 43)]
    out = pipe.generate(prompt, 12, 7, 0.7)
    again = pipe.generate(prompt, 12, 7, 0.7)
    assert np.array_equal(out["ids"], again["ids"]) and out["finite"]
    assert out["prefill_chunks"] == 3
    assert set(out["cache_bytes"]) == {"window", "full"}
    assert out["held_prefill"].shape == out["held_decode"].shape == (6,)
    assert out["rows_prefill"] >= int(out["held_prefill"].sum())
    want, _ = R.forward(CFG, params, jnp.asarray(prompt))
    assert close(out["prefill_logits"], want[-1])


def test_the_nodes_load_it_and_count_by_kind_of_layer():
    from comfyui_distributed_tpu.graph.nodes_builtin import (LLMLoader,
                                                             TPUPromptRewrite)
    from comfyui_distributed_tpu.models.registry import PRESETS
    from comfyui_distributed_tpu.telemetry import metrics as tm

    assert PRESETS["mimo-tiny"].kind == "llm"
    assert PRESETS["mimo-v2-flash"].llm == M.MimoConfig.mimo_stage()
    assert PRESETS["mimo-tiny"].llm == CFG

    def series(name, label):
        return {tuple(s["labels"][k] for k in label): s["value"]
                for s in tm.REGISTRY.snapshot()[name]["series"]}

    before = series("cdt_llm_attn_keys_total", ("layers", "phase"))
    (llm,) = LLMLoader().execute("mimo-tiny")
    (words,) = TPUPromptRewrite().execute(llm, "a paper boat at dusk", 11,
                                          prompt_tokens=21, new_tokens=6)
    (same,) = TPUPromptRewrite().execute(llm, "a paper boat at dusk", 11,
                                         prompt_tokens=21, new_tokens=6)
    assert words == same and len(words.split()) == 6
    want = llm.pipeline.config.attended_keys(21, 6)
    now = series("cdt_llm_attn_keys_total", ("layers", "phase"))
    moved = {k: v - before.get(k, 0.0) for k, v in now.items()}
    moved = {k: d for k, d in moved.items() if d or k in want}
    assert moved == {k: 2.0 * v for k, v in want.items()}
    assert moved["window", "prefill"] < moved["full", "prefill"]
    cache = series("cdt_llm_cache_bytes", ("layers",))
    assert cache["window",] == 5 * 4 * W * (12 + 8) * 4
    assert cache["full",] == 2 * 2 * 32 * (12 + 8) * 4


def test_the_two_cores_are_named_below_the_attention_layer(params):
    ids = ids_of(21)
    text = jax.jit(lambda i: M.prefill(CFG, params, i, 32)).lower(
        ids).compile().as_text()
    for scope in ("llm_full_core", "llm_swa_core"):
        assert f"cdt.llm_attn/{scope}/" in text, scope
    step = jax.jit(lambda c, t: M.decode_step(CFG, params, c, t, 21)).lower(
        M.empty_cache(CFG, 32), ids[0]).compile().as_text()
    for scope in ("llm_full_core", "llm_swa_core"):
        assert f"cdt.llm_attn/{scope}/" in step, scope


# --- the benchmark's files ---------------------------------------------------


def _cell(rehearsal=False):
    from cdtbench import workload

    return workload.assemble(CELL, rehearsal=rehearsal)


def test_the_configurations_file_is_the_preset_and_the_catalogs_row():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "mimo-v2-flash.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "mimo" and preset.kind == "llm"
    for key, value in dataclasses.asdict(preset.llm).items():
        if key == "dtype":
            assert held["llm"]["dtype"] == value
        else:
            assert held[key] == (list(value) if isinstance(value, tuple)
                                 else value), key
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    assert held["rotary_dim"] == preset.llm.rotary_dim == 64
    # every published width unchanged; three counts cut, and said
    assert held["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert {k: held["published"][k] for k in held["reduced"]} == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "vocab_size": 152576}
    published = {
        "attention_value_scale": 0.707, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384,
        "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
        "num_attention_heads": 64, "head_dim": 192,
        "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
        "rope_theta": 5000000, "tie_word_embeddings": False,
        "partial_rotary_factor": 0.334, "sliding_window": 128,
        "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "sliding_window_size": 128,
        "attention_chunk_size": 128, "moe_intermediate_size": 2048,
        "n_shared_experts": None, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "swa_head_dim": 192,
        "swa_v_head_dim": 128}
    for key, value in published.items():
        assert held[key] == value, key
    pattern = held["hybrid_layer_pattern"]
    assert len(pattern) == len(held["moe_layer_freq"]) == 48
    assert [i for i, p in enumerate(pattern) if p == 0] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert held["moe_layer_freq"] == [0] + [1] * 47
    assert held["layer_types"] == [
        "sliding_attention" if p else "full_attention" for p in pattern[:7]]
    assert held["source"] == "https://huggingface.co/XiaomiMiMo/" \
        "MiMo-V2-Flash/blob/main/config.json"
    assert held["llm"]["parameters"] == M.param_count(preset.llm)
    assert sum(n * (5 if "each of 5" in part else 1) for part, n
               in held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert held["llm"]["cache_bytes"] == llm_model.cache_bytes(
        M.MODEL, preset.llm, 131072 + 128)
    tree = M.init_mimo(preset.llm, None, abstract=True)
    assert held["llm"]["bytes"] + held["llm"]["rope_table_bytes"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assumed = [line for line in held["assumed"] if "ASSUMED" in line]
    assert len(assumed) == 2
    for words in ("the scale", "no q/k norm", "which 64", "the pairing"):
        assert any(words in line for line in assumed), words
    assert "16 chips share each layer; a pipeline stage of layers 0-6 " \
        "holding both ends of the vocabulary" in held["deployment"]
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    assert held["step_flops"] == sdxl["step_flops"]


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_mimo_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_mimo_reference.py").read_bytes()
    assert repo == copy


def test_the_cell_assembles_with_the_briefs_sizes_and_lists_no_reader():
    from cdtbench.kinds.mimo import request_sizes

    cell = _cell()
    assert cell.preset == "mimo-v2-flash" and cell.chips == 1
    assert request_sizes(cell) == (131072, 128)
    assert 131072 % cell.config["prefill_chunk_tokens"] == 0
    assert cell.step_key == "1024x1024.b2" and cell.step_flops
    bench = cell.bench
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": "mimo-v2-flash",
                     "traffic": "brief128k-sdxl8", "chips": 1,
                     "why": entry["why"]}
    (config,) = [c for c in bench["configs"] if c["name"] == "mimo-v2-flash"]
    assert config["file"] == "cdtbench/configs/mimo-v2-flash.json"
    assert config["reduced"] == cell.config["reduced"]
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    # per_layer was FULL when this cell came (128 of the contract's 128): the
    # ten ``mimo_*`` readers have their files and no cell lists them
    assert len(bench["per_layer"]) <= 128
    listed = {m["name"] for m in bench["per_layer"]}
    here = ROOT / "cdtbench" / "layer_metrics"
    for name in READERS:
        spec = json.loads((here / f"{name}.json").read_text())
        assert spec["what"] and spec["unit"] in ("ms", "%"), name
        assert (spec["reader"] == "python") == (here / f"{name}.py").exists()
    assert not listed & set(READERS) or listed >= set(READERS)
    assert {m["name"] for m in cell.metrics("per_layer")} >= {
        "host_overhead_ms", "denoise_ms_per_step", "device_idle_pct"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "request_p50_s", "images_per_s", "setup_s"}
    rehearsal = _cell(rehearsal=True)
    assert rehearsal.preset == "mimo-tiny"
    assert request_sizes(rehearsal) == (40, 16)


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    from cdtbench.kinds import mimo

    config = _cell().config
    cfg = M.MimoConfig.mimo_stage()
    assert mimo.layer_counts(config) == {"full": 2, "window": 5}
    assert mimo.attention_params(config, "full") == 89_128_960
    assert mimo.attention_params(config, "window") + 64 == 94_371_904
    assert mimo.expert_params(config) == 25_165_824
    assert mimo.parameters(config) == M.param_count(cfg) \
        == config["llm"]["parameters"]
    # a pair of every head: 64 × 2 × (192 + 128), never 256 + 128
    assert mimo.pair_flops(config) == 64 * 640
    pairs = cfg.attended_keys(131072, 128)
    full = mimo.attention_core_flops(config, pairs["full", "prefill"])
    band = mimo.attention_core_flops(config, pairs["window", "prefill"])
    assert full == pytest.approx(704e12, rel=1e-3)
    assert band / 5 == pytest.approx(0.69e12, rel=1e-2)
    held = 131072 * 8 * 6 / 16.0
    total = mimo.prefill_flops(
        config, 131072, pairs["full", "prefill"] + pairs["window", "prefill"],
        held)
    by_hand = 2.0 * 131072 * (2 * 89_128_960 + 5 * 94_371_840
                              + 201_326_592 + 6 * 4096 * 256) \
        + full + band + 2.0 * held * 25_165_824 + 2.0 * 19072 * 4096
    assert total == pytest.approx(by_hand, rel=1e-12)
    assert 0.70 < (full + band) / total < 0.78
    # a decoded token: weights once with the even held share, the valid rows
    need = mimo.decode_bytes_per_token(config, 1 / 16.0, 131072, 128)
    weights = 2 * (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592
                   + 6 * 4096 * 256 + 19073 * 4096) \
        + 4 * (5 * 64 + 15 * 4096 + 6 * 256) \
        + 0.5 * 6 * 2 * 25_165_824
    rows = 2 * (131072 + 64) * 4 * 320 * 2 + 5 * 128 * 8 * 320 * 2
    assert need == pytest.approx(weights + rows, rel=1e-12)
    assert rows == pytest.approx(0.67e9, rel=1e-2)
    assert need == pytest.approx(2.65e9, rel=2e-2)


def _snapshot(requests, seconds):
    cfg = M.MimoConfig.mimo_stage()
    pairs = cfg.attended_keys(131072, 128)
    slots = {"prefill": 131072 * 48, "decode": 128 * 48}
    return {
        "cdt_llm_attn_keys_total": {"series": [
            {"labels": {"layers": kind, "phase": phase},
             "value": requests * n} for (kind, phase), n in pairs.items()]},
        "cdt_llm_tokens_total": {"series": [
            {"labels": {"phase": phase}, "value": requests * tokens}
            for phase, tokens in (("prefill", 131072), ("decode", 128))]},
        "cdt_llm_expert_slots_total": {"series": [
            {"labels": {"phase": phase, "where": where},
             "value": requests * n * share}
            for phase, n in slots.items()
            for where, share in (("held", 1 / 16), ("absent", 15 / 16))]},
        "cdt_llm_cache_bytes": {"series": [
            {"labels": {"layers": "window"}, "value": 3276800},
            {"labels": {"layers": "full"}, "value": 681574400}]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 10 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock(
        monkeypatch):
    from cdtbench import readers, workload
    from cdtbench.kinds import mimo

    cell = _cell()
    scopes = {"full": 4.0, "window": 0.5}
    monkeypatch.setattr(mimo, "scope_seconds",
                        lambda ctx: scopes if ctx.get("trace") else None)
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 8.0}] * 2,
           "opened": _snapshot(1, 1.0), "closed": _snapshot(3, 1.0 + 1.28),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 7.5,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 0.6, "count": 1},
                         "llm_prefill": {"seconds": 6.4, "count": 1}},
                     "op_seconds": {"gqa_wide_causal_mha.3": 3.9,
                                    "gqa_causal_mha.1": 9.9,
                                    "fusion.7": 1.0}}}
    config = cell.config
    pairs = M.MimoConfig.mimo_stage().attended_keys(131072, 128)
    assert readers.read("mimo_decode_ms_per_token", ctx) \
        == pytest.approx(5.0)
    assert readers.read("mimo_prefill_ms", ctx) == pytest.approx(6400.0)
    assert readers.read("mimo_share_pct", ctx) == pytest.approx(
        100 * 11 * 1.28 / 16.0)
    assert readers.read("mimo_window_cache_pct", ctx) == pytest.approx(
        100 * 3276800 / (3276800 + 681574400))
    assert readers.read("mimo_held_slot_pct", ctx) == pytest.approx(6.25)
    flops = mimo.prefill_flops(
        config, 131072, pairs["full", "prefill"] + pairs["window", "prefill"],
        131072 * 3)
    assert readers.read("mimo_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 6.4, rel=1e-6)
    assert readers.read("mimo_decode_hbm_pct", ctx) == pytest.approx(
        100 * mimo.decode_bytes_per_token(config, 1 / 16, 131072, 128)
        / 819e9 / (0.6 / 128), rel=1e-6)
    # the kernel's own name, not Trinity's; the EXACT count: under 83.3
    assert readers.read("mimo_full_core_mxu_pct", ctx) == pytest.approx(
        100 * mimo.attention_core_flops(config, pairs["full", "prefill"])
        / 197e12 / 3.9, rel=1e-6)
    assert readers.read("mimo_window_core_pct", ctx) == pytest.approx(
        100 * 0.5 / 7.5)
    assert readers.read("mimo_attn_core_pct", ctx) == pytest.approx(
        100 * 4.5 / 7.5)
    shares = ("mimo_prefill_mfu_pct", "mimo_decode_hbm_pct",
              "mimo_full_core_mxu_pct", "mimo_window_core_pct",
              "mimo_attn_core_pct")
    for name in shares:
        assert 0 < readers.read(name, ctx) < 100, name
    # no trace, a trace without the scopes or the kernel (the parent), or a
    # program without the series: nothing, not zero, and never a raise
    for name in shares:
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    monkeypatch.setattr(mimo, "scope_seconds", lambda ctx: None)
    for name in ("mimo_window_core_pct", "mimo_attn_core_pct"):
        assert readers.read(name, ctx) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    assert readers.read("mimo_full_core_mxu_pct",
                        {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in READERS:
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of the python ones
    other = workload.assemble("trinity-large-preview.brief128k-sdxl8")
    for name in ("mimo_decode_ms_per_token", "mimo_share_pct") + shares:
        assert readers.read(name, {**ctx, "cell": other}) is None, name


def test_the_scopes_reader_finds_nothing_without_a_profile():
    from cdtbench.kinds import mimo

    ctx = {"cell": _cell(), "trace": {"busy_s": 1.0}}
    assert mimo.scope_seconds({**ctx, "trace": None}) is None
    assert mimo.core_pct({**ctx, "trace": None}, ("window",)) is None


@pytest.mark.parametrize("arm", ["no_sink", "window_256", "one_theta",
                                 "no_value_scale", "kv_fp8", "rope_all_192"])
def test_the_parity_tools_arms_change_what_the_program_computes(params, arm):
    from cdtbench import parity_mimo as P

    ids = ids_of(21 + 6, key=9)
    want, _ = R.forward(CFG, params, ids)
    with P.lowered(CFG, arm):
        got, _ = through_the_cache(CFG, P.lowered_weights(params, arm), ids,
                                   21)
    assert not close(got, want[20:], 10 * F32_TOL), arm
    assert close(through_the_cache(CFG, params, ids, 21)[0], want[20:])


def test_the_tools_walk_of_the_reference_is_the_references_forward(params):
    from cdtbench import parity_mimo as P
    from cdtbench import parity_trinity as PT

    reference = P.load_reference()
    T, N = 21, 6
    ids = np.asarray(ids_of(T + N, key=9))
    walk = PT.prompt_walk(reference, CFG, params, ids[:T], 7)
    assert len(walk) == 7
    assert walk[0][0].shape == (T, 2, 12) and walk[1][1].shape == (T, 4, 8)
    positions = [T - 1, T, T + 3, T + N - 1]
    got = PT.tail_logits(reference, CFG, params, walk, ids, T, positions)
    want, _ = R.forward(CFG, params, jnp.asarray(ids))
    assert close(got, want[jnp.asarray(positions)], 1e-5)


def test_the_parity_tool_rehearses_and_its_limits_are_data(capsys):
    from cdtbench import parity_mimo as P

    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "mimo-v2-flash.parity.json").read_text())
    assert set(limits["limits"]) == {"best_decode_row_rel_l2",
                                     "median_row_rel_l2",
                                     "worst_row_rel_l2"}
    assert all(0 < v["limit"] < 1 and v["reason"]
               for v in limits["limits"].values())
    assert P.main(["--workload", CELL, "--rehearse", "--seeds", "3",
                   "--degrade", "none,no_sink"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert [x["degrade"] for x in lines] == ["none", "no_sink"]
    assert lines[0]["inside_tolerances"] and lines[1]["seeds_failed"] == 1


def test_the_golden_names_a_request_and_holds_an_image():
    from cdtbench import golden

    spec = golden.spec_of(CELL)
    assert spec["request"]["seed"] > 0 and spec["request"]["prompt"]
    assert (golden.HERE / f"{CELL}.png").exists()
