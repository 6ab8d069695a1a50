"""The fifth prompt rewriter (grouped-query attention over window and full
layers mixed, rope on the window layers only, a gated sandwich-norm block,
routed experts beside a shared one) at the tiny float32 preset, against the
plain reference on seeded weights: a whole prefill, the chunked prefill
(chunk = window) through both forms of the kernel, a prompt ending inside a
chunk, decode through ring and buffer far past the window, what a padded
chunk owes a ring, the shares adding up to the uncut layer, the older expert
models' forms unmoved, the shared pipeline and nodes with the new counter,
and the benchmark's files, counts, readers and parity tool of the cell."""

import contextlib
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.models import llm_trinity as M
from comfyui_distributed_tpu.models import llm_trinity_reference as R
from comfyui_distributed_tpu.ops import expert_share, gqa_attention

ROOT = Path(__file__).resolve().parent.parent
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = M.TrinityConfig.tiny()
CELL = "trinity-large-preview.brief128k-sdxl8"
W = CFG.sliding_window


@pytest.fixture(scope="module")
def params():
    return M.init_trinity(CFG, jax.random.key(0))


def ids_of(n, key=1):
    return jax.random.randint(jax.random.key(key), (n,), 0, CFG.vocab_size)


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# --- prefill through ring and buffer, decode through both ---------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.layer_types.count(M.FULL) == 1
    assert CFG.layer_types.count(M.SLIDING) == 4
    assert CFG.moe_layers == [1, 2, 3, 4] and not CFG.is_moe(0)
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 3
    assert CFG.router_experts > CFG.num_experts
    assert CFG.prefill_chunk_tokens == W and CFG.embed_scale == math.sqrt(32)
    share = M.TrinityConfig.trinity_share()
    assert (share.num_attention_heads, share.num_key_value_heads,
            share.head_dim, share.sliding_window) == (48, 8, 128, 4096)
    assert share.routing == expert_share.Routing(256, 4, 1, 1, 2.448)
    with pytest.raises(ValueError, match="the chunk is the window"):
        M.TrinityConfig.tiny(prefill_chunk_tokens=4)


# a prompt shorter than the window (whole: one short chunk), exactly one
# chunk, whole chunks, and two that end INSIDE a chunk
@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("T", [5, 8, 24, 21, 37])
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


@pytest.mark.parametrize("T", [5, 16, 21])
def test_decode_runs_through_ring_and_buffer_far_past_the_window(params, T):
    """30 decoded tokens are almost four windows: every ring slot is
    overwritten several times, and after a prompt that ends inside a chunk
    (21 = 2 × 8 + 5) the FIRST token still finds the 3 rows of the chunk
    before in their slots."""
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


def test_a_padded_chunk_writes_only_its_valid_rows_into_a_ring(params):
    """The contract ``chunked_prefill`` states for a ring: row ``p ≥ T``
    would land on the slot of ``p − window``."""
    ids = ids_of(2 * W, key=3)
    _, cache, _, _ = M.prefill_chunk(
        CFG, params, M.empty_cache(CFG, 3 * W), ids[:W], 0, W)
    before = jax.tree_util.tree_map(np.asarray, cache)
    _, after, _, _ = M.prefill_chunk(CFG, params, cache, ids[W:], W, 3)
    for i in range(CFG.num_hidden_layers):
        for leaf in ("k", "v"):
            old, new = before[leaf][i], np.asarray(after[leaf][i])
            if CFG.is_full(i):
                assert np.array_equal(new[:, :W], old[:, :W])
                assert np.abs(new[:, W:W + 3]).sum() > 0
            else:
                assert np.array_equal(new[:, 3:], old[:, 3:])   # kept
                assert not np.array_equal(new[:, :3], old[:, :3])
    # and a chunk longer than the window is refused, not mis-slotted
    with pytest.raises(ValueError, match="outruns the window"):
        M.prefill_chunk(CFG, params, cache, ids_of(W + 1), 0, W + 1)


def test_rope_turns_the_window_layers_and_only_them(params):
    ids = ids_of(21)
    still = {**params, "rope": {
        "cos": jnp.ones_like(params["rope"]["cos"]),
        "sin": jnp.zeros_like(params["rope"]["sin"])}}
    roped, _, _ = M.prefill(CFG, params, ids, 21, all_logits=True)
    flat, _, _ = M.prefill(CFG, still, ids, 21, all_logits=True)
    assert not close(roped, flat, 1e-3)
    every_full = dataclasses.replace(CFG, layer_types=(M.FULL,) * 5)
    a, _, _ = M.prefill(every_full, params, ids, 21, all_logits=True)
    b, _, _ = M.prefill(every_full, still, ids, 21, all_logits=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    want, _ = R.forward(every_full, params, ids)
    assert close(a, want)


def test_the_rope_table_is_made_in_float64():
    """At position 131 071 a float32 ``p · θ^(−2k/d)`` is off by more than
    the parity limits forgive; the table is the float64 angle's."""
    cfg = M.TrinityConfig.tiny(max_position_embeddings=131072, head_dim=128)
    table = M.rope_table(cfg)
    p, k = 131071, 1
    exact = math.cos(p * 10000.0 ** (-k / 64))
    assert abs(float(table["cos"][p, k]) - exact) < 1e-6
    low = np.float32(p) * np.float32(10000.0) ** np.float32(-k / 64)
    assert abs(math.cos(float(low)) - exact) > 1e-4
    cos, _ = R.rope_angles(cfg, 131072)
    assert np.array_equal(np.asarray(cos), np.asarray(table["cos"]))


def test_the_band_is_window_keys_the_querys_own_included(params):
    """Position ``p``'s logits do not move with a token more than ``window
    − 1`` places before it on ONE window layer, and do with the one just
    inside."""
    sliding = dataclasses.replace(CFG, layer_types=(M.SLIDING,) * 5)
    ids = ids_of(24, key=4)
    p = 20
    # five layers carry a change five windows far: one layer alone
    one = dataclasses.replace(sliding, num_hidden_layers=1,
                              num_dense_layers=1, layer_types=(M.SLIDING,))
    cut = {**params, "layers": params["layers"][:1]}
    base, _, _ = M.prefill(one, cut, ids, 24, all_logits=True)
    outside = ids.at[p - W].set((ids[p - W] + 1) % CFG.vocab_size)
    inside = ids.at[p - W + 1].set((ids[p - W + 1] + 1) % CFG.vocab_size)
    moved_out, _, _ = M.prefill(one, cut, outside, 24, all_logits=True)
    moved_in, _, _ = M.prefill(one, cut, inside, 24, all_logits=True)
    assert np.array_equal(np.asarray(moved_out[p]), np.asarray(base[p]))
    assert not np.array_equal(np.asarray(moved_in[p]), np.asarray(base[p]))


def test_a_bfloat16_run_fails_the_float32_tolerance(params):
    ids = ids_of(21)
    want, _ = R.forward(CFG, params, ids)
    low = dataclasses.replace(CFG, dtype="bfloat16")
    logits, _, _ = M.prefill(low, params, ids, 21, all_logits=True)
    assert not close(logits, want)
    # a flipped expert moves single rows by tenths; most rows are rounding
    rows = np.linalg.norm(np.asarray(logits) - np.asarray(want), axis=1) \
        / np.linalg.norm(np.asarray(want), axis=1)
    assert 1e-3 < np.median(rows) < 0.05


# --- the share of the experts -------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 4 of 16 experts each: their held parts, with what
    every chip computes alike (the shared expert; attention and the dense
    layer are outside the expert layer) counted ONCE, are the uncut
    reference's expert layer, and a whole block built on the sum is the
    uncut reference's block."""
    uncut = M.TrinityConfig.tiny(num_experts=16)
    params = M.init_trinity(uncut, jax.random.key(3))
    ids = ids_of(19, key=5)
    i, layer = 2, params["layers"][2]                     # the full layer
    m = layer["moe"]
    x = jax.random.normal(jax.random.key(6), (19, uncut.hidden_size))
    want, slots = R.experts(uncut, R._f32(m), x)
    assert int(slots) == 19 * uncut.num_experts_per_tok
    idx, w = expert_share.route(x, m["w_router"], m["router_bias"],
                                uncut.routing)
    total, held = M._swiglu(x, m["shared"], jnp.float32), 0
    for first in range(0, 16, 4):
        part, _ = expert_share.held_part(
            x, idx, w, m["e_gu"][first:first + 4], m["e_down"][first:first + 4],
            first, jnp.float32, uncut.routing, tile=uncut.expert_tile)
        total = total + part
        held += int(expert_share.held_slots(idx, first, 4).sum())
    assert held == 19 * uncut.num_experts_per_tok
    assert close(total, want, 1e-5)
    # the whole block: the reference's layer on the uncut model, against
    # attention once + the summed expert layer under the sandwich's norm
    h = R.embed(uncut, params, ids)
    cos, sin = R.rope_angles(uncut, 19)
    k, v = R.keys_values(uncut, False, layer, h, cos, sin)
    block, _ = R.layer_rows(uncut, False, True, layer, h, jnp.arange(19), k,
                            v, cos, sin)
    one = dataclasses.replace(uncut, num_hidden_layers=1, num_dense_layers=0,
                              layer_types=(M.FULL,))
    # ``_ffn`` runs on the block's own attention output: rebuild that half
    q, kk, vv, gate = M._attn_in(one, layer["attn"], M.rms_norm(
        h, layer["norm_in"], one.rms_norm_eps), None)
    o = gqa_attention.causal_chunk(q, M._rows(kk, jnp.float32),
                                   M._rows(vv, jnp.float32), 0,
                                   one.head_dim ** -0.5, jnp.float32, 4, 4)
    mid = M._add_normed(h, M._attn_out(one, layer["attn"], o, gate),
                        layer["norm_attn_out"], one.rms_norm_eps)
    served, _, _ = M._ffn(one, layer, 0, mid, jnp.ones((19,), bool))
    assert close(served, block, 1e-5)


# (preset, prompt tokens) -> (chunk, chunks, form): the three older expert
# models and the state-space one as their cells run them, and this one
PLANS = {("ling-3.0-flash-vl", 1024): (1024, 1, "dense"),
         ("motif-3-beta", 1024): (1024, 1, "dense"),
         ("kimi-k2.6", 32768): (4096, 8, "grouped"),
         ("ai21-jamba2-3b", 65536): (4096, 16, None),
         ("trinity-large-preview", 131072): (4096, 32, "grouped")}


@pytest.mark.parametrize("preset,tokens", sorted(PLANS))
def test_every_rewriters_prefill_takes_the_form_it_took(preset, tokens):
    from comfyui_distributed_tpu.models.registry import PRESETS

    pipe = pipeline_llm.LLMPipeline(PRESETS[preset].llm, None)
    assert pipe.prefill_plan(tokens) == PLANS[preset, tokens]


def test_this_cell_sits_exactly_at_the_forms_edge():
    """4096 rows × top 4 ÷ 256 experts = 64 rows an expert a chunk: half a
    tile of 128, where ``prefill_form`` turns to the grouped form."""
    r = M.TrinityConfig.trinity_share().routing
    assert expert_share.prefill_form(4096, r) == "grouped"
    assert expert_share.prefill_form(4095, r) == "dense"
    assert 2 * 4096 * r.per_token == expert_share.GROUP_TILE * r.experts


# --- the weights and the cache ------------------------------------------------


def test_the_share_counts_what_the_issue_counted():
    cfg = M.TrinityConfig.trinity_share()
    assert M.param_count(cfg) == 2_509_964_544
    tree = M.init_trinity(cfg, None, abstract=True)
    layer = tree["layers"][1]
    assert layer["attn"]["w_in"].shape == (3072, 14336)
    assert layer["moe"]["e_gu"].shape == (16, 3072, 6144)
    assert layer["moe"]["w_router"].shape == (3072, 256)
    assert tree["rope"]["cos"].shape == (262144, 64)
    assert tree["rope"]["cos"].dtype == jnp.float32
    assert "ffn" in tree["layers"][0] and "moe" not in tree["layers"][0]
    sizes = llm_model.cache_bytes(cfg.model, cfg, 131072 + 128)
    assert sizes["window"] == 4 * 2 * 8 * 4096 * 128 * 2 == 4 * 16 * 2 ** 20
    # 131 200 rows rounded up to the full layer's K block of 2048
    assert sizes["full"] == 2 * 8 * 133120 * 128 * 2
    share = 100 * sizes["window"] / (sizes["window"] + sizes["full"])
    assert share == pytest.approx(11.0, abs=0.1)


def test_attended_keys_are_the_masks_own_count():
    T, N = 21, 6
    pairs = CFG.attended_keys(T, N)
    row = np.arange(T + N)[:, None]
    col = np.arange(T + N)[None, :]
    causal = col <= row
    band = causal & (row - col < W)
    assert pairs["full", "prefill"] == causal[:T].sum()
    assert pairs["window", "prefill"] == 4 * band[:T].sum()
    assert pairs["full", "decode"] == causal[T:].sum()
    assert pairs["window", "decode"] == 4 * band[T:].sum()
    big = M.TrinityConfig.trinity_share().attended_keys(131072, 128)
    assert big["window", "prefill"] / 4 == pytest.approx(131072 * 4096,
                                                         rel=0.02)
    assert big["full", "prefill"] == 131072 * 131073 // 2


# --- the shared pipeline, the nodes, the counter ------------------------------


def test_the_pipeline_runs_it_like_the_other_four(params):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model.prefill_chunk is M.prefill_chunk
    assert pipe.prefill_plan(40) == (8, 5, "grouped")
    prompt = [int(i) % CFG.vocab_size for i in range(3, 43)]
    out = pipe.generate(prompt, 12, 7, 0.7)
    again = pipe.generate(prompt, 12, 7, 0.7)
    assert np.array_equal(out["ids"], again["ids"]) and out["finite"]
    assert out["prefill_chunks"] == 5 and out["prefill_form"] == "grouped"
    assert set(out["cache_bytes"]) == {"window", "full"}
    assert out["held_prefill"].shape == out["held_decode"].shape == (4,)
    assert out["rows_prefill"] >= int(out["held_prefill"].sum())
    want, _ = R.forward(CFG, params, jnp.asarray(prompt))
    assert close(out["prefill_logits"], want[-1])


def test_the_nodes_load_it_and_count_its_attended_keys():
    from comfyui_distributed_tpu.graph.nodes_builtin import (LLMLoader,
                                                             TPUPromptRewrite)
    from comfyui_distributed_tpu.models.registry import PRESETS
    from comfyui_distributed_tpu.telemetry import metrics as tm

    assert PRESETS["trinity-tiny"].kind == "llm"
    assert PRESETS["trinity-large-preview"].llm \
        == M.TrinityConfig.trinity_share()

    def keys():
        snapshot = tm.REGISTRY.snapshot()["cdt_llm_attn_keys_total"]
        return {(s["labels"]["layers"], s["labels"]["phase"]): s["value"]
                for s in snapshot["series"]}

    before = keys()
    (llm,) = LLMLoader().execute("trinity-tiny")
    (words,) = TPUPromptRewrite().execute(llm, "a lighthouse at dusk", 11,
                                          prompt_tokens=21, new_tokens=6)
    (same,) = TPUPromptRewrite().execute(llm, "a lighthouse at dusk", 11,
                                         prompt_tokens=21, new_tokens=6)
    assert words == same and len(words.split()) == 6
    want = llm.pipeline.config.attended_keys(21, 6)
    # another model's kinds of layer may stand in the registry (a test file
    # that ran earlier in this process): they did not move
    moved = {k: v - before.get(k, 0.0) for k, v in keys().items()}
    moved = {k: d for k, d in moved.items() if d or k in want}
    assert moved == {k: 2.0 * v for k, v in want.items()}
    assert moved["window", "prefill"] < 4 * moved["full", "prefill"]


# --- the benchmark's files ----------------------------------------------------


def _cell(rehearsal=False):
    sys.path.insert(0, str(ROOT))
    from cdtbench import workload

    return workload.assemble(CELL, rehearsal=rehearsal)


TRINITY_METRICS = [
    "trinity_prefill_ms", "trinity_decode_ms_per_token", "trinity_share_pct",
    "trinity_prefill_mfu_pct", "trinity_decode_hbm_pct",
    "trinity_full_core_mxu_pct", "trinity_window_core_mxu_pct",
    "trinity_attn_core_pct", "trinity_window_cache_pct",
    "trinity_held_slot_pct", "trinity_expert_rows_per_slot"]


def test_the_configurations_file_is_the_preset_and_the_catalogs_row():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "trinity-large-preview.json").read_text())
    cfg = PRESETS[held["preset"]].llm
    assert PRESETS[held["rehearsal_preset"]].llm == M.TrinityConfig.tiny()
    for field in dataclasses.fields(cfg):
        # the file's ``attn_block_q/k`` are PR 39's one pair for both
        # kernels: documentation no code reads, the benchmark's to correct
        # (PERF.md §7); the served tiles are the preset's alone
        if field.name in ("layer_types", "dtype") \
                or field.name.startswith("attn_"):
            continue
        assert held[field.name] == getattr(cfg, field.name), field.name
    assert tuple(held["layer_types_kept"]) == cfg.layer_types
    assert held["llm"]["dtype"] == cfg.dtype
    assert held["llm"]["parameters"] == M.param_count(cfg)
    tree = M.init_trinity(cfg, None, abstract=True)
    rope = tree.pop("rope")
    size = lambda t: sum(math.prod(a.shape) * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    assert held["llm"]["bytes"] == size(tree)
    assert held["llm"]["rope_table_bytes"] == size(rope)
    assert sum(n * (4 if "each of 4" in part else 1) for part, n in
               held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size"]
    assert held["reduced"] == reduced == list(held["reduced_why"])
    assert held["published"]["num_experts"] == held["router_experts"] == 256
    assert held["published"]["num_hidden_layers"] == 60
    assert held["layers_kept"] == [5, 6, 7, 8, 9] and "16 chips" in \
        held["deployment"]
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-large-preview")
    assert entry["reduced"] == reduced and entry["source"] == held["source"]
    # every key of the catalog's config, under its key, unchanged but the
    # four cuts; the kept layers are five consecutive ones of its list
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"Trinity-Large-Preview"' in line)
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in reduced:
                assert held[key] == value, key
        assert catalog["config"]["layer_types"][5:10] \
            == held["layer_types_kept"]
        assert catalog["config"]["num_dense_layers"] == 6    # 5: the last


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_trinity_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_trinity_reference.py").read_bytes()
    assert repo == copy


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench.kinds.trinity import request_sizes

    cell = _cell()
    assert cell.preset == "trinity-large-preview" and cell.chips == 1
    assert request_sizes(cell) == (131072, 128)
    assert cell.graph["9"]["inputs"]["temperature"] == 0.7
    assert (cell.steps, cell.cfg, cell.step_key) == (8, 6.0, "1024x1024.b2")
    assert cell.traffic["clients"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["warmup_requests"] == 2
    assert cell.config["serve_env"] == {}
    small = _cell(rehearsal=True)
    assert small.preset == "trinity-tiny"
    assert small.graph["1"]["inputs"]["ckpt_name"] == "tiny"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= set(TRINITY_METRICS) | {
        "denoise_ms_per_step", "peak_hbm_gib", "denoise_mfu_pct",
        "device_idle_pct", "host_overhead_ms"}
    assert not {n for n in names
                if n.startswith(("llm_", "motif_", "kimi_", "jamba_"))}
    bench = cell.bench
    ours = [m for m in bench["per_layer"] if m["name"].startswith("trinity_")]
    assert [m["name"] for m in ours] == TRINITY_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "request_p50_s"
               for m in ours)
    # appended as one block and the cell after the seven before it (later
    # PRs append after both)
    first = bench["per_layer"].index(ours[0])
    assert bench["per_layer"][first:first + 11] == ours
    assert bench["workloads"][7]["name"] == CELL
    import cdtbench.workload as workload

    for other in ("kimi-k2.6.brief32k-sdxl8", "ai21-jamba2-3b.brief64k-sdxl8",
                  "sdxl-base.solo30"):
        assert not {m["name"] for m in workload.assemble(other).metrics(
            "per_layer")} & set(TRINITY_METRICS)


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    from cdtbench.kinds.trinity import (attention_core_flops,
                                        decode_bytes_per_token, layer_counts,
                                        prefill_flops)

    cell = _cell()
    config, T = cell.config, 131072
    assert layer_counts(config) == {"full": 1, "window": 4}
    full = attention_core_flops(config, T, "full")
    window = attention_core_flops(config, T, "window")
    assert full == pytest.approx(211e12, rel=3e-3)
    assert window == pytest.approx(52e12, rel=1e-2)
    assert full / (window / 4) == pytest.approx(16.0, rel=0.02)
    # the slots the program counts: all of them held = 1/16 of 4 a token
    held = T * 4 * 4 / 16
    flops = prefill_flops(config, T, held)
    assert flops - full - window == pytest.approx(150e12, rel=2e-2)
    # a held slot more is one row of one expert more
    assert prefill_flops(config, T, held + 1) - flops == 2 * 3 * 3072 * 3072
    cfg = M.TrinityConfig.trinity_share()
    tree = M.init_trinity(cfg, None, abstract=True)
    tree.pop("rope")
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(tree))
    experts = sum(math.prod(layer["moe"][k].shape) * 2
                  for layer in tree["layers"] if "moe" in layer
                  for k in ("e_gu", "e_down"))
    embed = math.prod(tree["embed"].shape) * 2
    rows = 131072 + 64
    want = weights - experts - embed + 3072 * 2 \
        + (rows + 4 * 4096) * 2 * 8 * 128 * 2 \
        + 0.0625 * 4 * 4 * 3 * 3072 * 3072 * 2
    got = decode_bytes_per_token(config, 0.0625, 131072, 128)
    assert abs(got - want) / want < 1e-9
    assert 1.85e9 < got < 1.95e9                         # the issue's 1.90 GB


def _snapshot(rows, held, seconds):
    return {
        "cdt_llm_expert_rows_total": {"series": [
            {"labels": {"form": "grouped"}, "value": rows},
            {"labels": {"form": "token"}, "value": 32.0 * seconds}]},
        "cdt_llm_expert_slots_total": {"series": [
            {"labels": {"where": "held", "phase": "prefill"}, "value": held},
            {"labels": {"where": "absent", "phase": "prefill"},
             "value": 15 * held},
            {"labels": {"where": "held", "phase": "decode"},
             "value": 128.0 * seconds},
            {"labels": {"where": "absent", "phase": "decode"},
             "value": 1920.0 * seconds}]},
        "cdt_llm_cache_bytes": {"series": [
            {"labels": {"layers": "window"}, "value": 64.0},
            {"labels": {"layers": "full"}, "value": 516.0}]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 3 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock():
    from cdtbench import readers
    from cdtbench.kinds.trinity import (attention_core_flops,
                                        decode_bytes_per_token, prefill_flops)

    cell = _cell()
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 6.0}] * 2,
           "opened": _snapshot(1000.0, 500.0, 1.0),
           "closed": _snapshot(1000.0 + 2 * 262144, 500.0 + 2 * 131072,
                               1.0 + 2 * 0.384),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 5.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 0.4, "count": 1},
                         "llm_prefill": {"seconds": 3.5, "count": 1}},
                     "op_seconds": {"gqa_causal_mha.1": 1.7,
                                    "gqa_window_mha.1": 0.3,
                                    "gqa_window_mha.2": 0.25,
                                    "fusion.7": 1.0}}}
    assert readers.read("trinity_decode_ms_per_token", ctx) \
        == pytest.approx(3.0)
    assert readers.read("trinity_prefill_ms", ctx) == pytest.approx(1152.0)
    assert readers.read("trinity_share_pct", ctx) == pytest.approx(
        100 * 4 * 0.768 / 12.0)
    assert readers.read("trinity_held_slot_pct", ctx) == pytest.approx(6.25)
    assert readers.read("trinity_window_cache_pct", ctx) == pytest.approx(
        100 * 64 / 580)
    assert readers.read("trinity_expert_rows_per_slot", ctx) \
        == pytest.approx(2.0)
    assert readers.read("trinity_attn_core_pct", ctx) == pytest.approx(45.0)
    need = decode_bytes_per_token(cell.config, 0.0625, 131072, 128)
    assert readers.read("trinity_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / (0.4 / 128), rel=1e-9)
    assert readers.read("trinity_prefill_mfu_pct", ctx) == pytest.approx(
        100 * prefill_flops(cell.config, 131072, 131072.0) / 197e12 / 3.5,
        rel=1e-9)
    for kind, seconds in (("full", 1.7), ("window", 0.55)):
        assert readers.read(f"trinity_{kind}_core_mxu_pct", ctx) \
            == pytest.approx(100 * attention_core_flops(
                cell.config, 131072, kind) / 197e12 / seconds, rel=1e-9)
    # every share stays a share for any time the chip could take: the
    # counted work over the peak is the least time there is
    assert attention_core_flops(cell.config, 131072, "full") / 197e12 > 1.0
    # no trace, a trace without the kernels, or a program without the
    # series (the parent): nothing, not zero, and nothing raised
    traced = ("trinity_decode_hbm_pct", "trinity_prefill_mfu_pct",
              "trinity_full_core_mxu_pct", "trinity_window_core_mxu_pct",
              "trinity_attn_core_pct")
    for name in traced:
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    for name in traced[2:]:
        assert readers.read(name, {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("trinity_decode_ms_per_token", "trinity_prefill_ms",
                 "trinity_share_pct", "trinity_expert_rows_per_slot",
                 "trinity_held_slot_pct", "trinity_window_cache_pct",
                 "trinity_decode_hbm_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of the python ones
    import cdtbench.workload as workload

    kimi = workload.assemble("kimi-k2.6.brief32k-sdxl8")
    for name in ("trinity_decode_hbm_pct", "trinity_decode_ms_per_token",
                 "trinity_share_pct", "trinity_prefill_mfu_pct",
                 "trinity_full_core_mxu_pct", "trinity_window_core_mxu_pct",
                 "trinity_expert_rows_per_slot"):
        assert readers.read(name, {**ctx, "cell": kimi}) is None, name


def _parity():
    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_trinity

    return parity_trinity


@pytest.mark.parametrize("arm", ["kv_fp8", "stream_bf16", "weights_fp8",
                                 "rope_on_full", "band_wide", "no_gate",
                                 "no_sandwich", "no_qk_norm", "no_bias",
                                 "no_mup"])
def test_the_parity_tools_arms_change_what_the_program_computes(params, arm):
    """The ten arms that must fail on the chip are built around the served
    code: here they only have to move the logits, and leave no trace."""
    tool = _parity()
    assert set(tool.DEGRADE) == {"none"} | set(tool.LOWER) \
        | set(tool.LEFT_OUT)
    ids40 = [int(i) % CFG.vocab_size for i in range(3, 43)]
    cfg = CFG
    if arm == "weights_fp8":       # what is HELD in bfloat16 goes to fp8
        cfg = dataclasses.replace(CFG, dtype="bfloat16")
        params = M.init_trinity(cfg, jax.random.key(0))

    def run(weights, around=contextlib.nullcontext):
        with around():
            return pipeline_llm.LLMPipeline(cfg, weights).generate(
                ids40, 8, 1, 0.7)

    sound = run(params)
    held = tool.lowered_weights(params, arm)
    if arm == "weights_fp8":
        assert held["embed"].dtype == jnp.float8_e4m3fn
        assert held["layers"][1]["moe"]["e_gu"].dtype == jnp.float8_e4m3fn
        assert held["final_norm"].dtype == held["rope"]["cos"].dtype \
            == jnp.float32
    elif arm == "no_bias":
        assert not np.asarray(held["layers"][2]["moe"]["router_bias"]).any()
        assert held["layers"][2]["moe"]["e_gu"] \
            is params["layers"][2]["moe"]["e_gu"]
    else:
        assert held is params
    low = run(held, lambda: tool.lowered(cfg, arm))
    assert not close(low["prefill_logits"], sound["prefill_logits"], 1e-4)
    again = run(params)
    assert np.array_equal(np.asarray(again["prefill_logits"]),
                          np.asarray(sound["prefill_logits"]))


def test_the_tools_walk_of_the_reference_is_the_references_forward(params):
    tool = _parity()
    reference = tool.load_reference()
    ids = np.asarray(ids_of(21 + 9, key=8))
    want, _ = R.forward(CFG, params, jnp.asarray(ids))
    walk = tool.prompt_walk(reference, CFG, params, ids[:21].tolist(), 8)
    assert len(walk) == 5 and walk[2][0].shape == (21, 2, 8)
    positions = [20, 23, 29]
    got = tool.tail_logits(reference, CFG, params, walk, ids, 21, positions)
    assert close(got, want[jnp.asarray(positions)], 1e-6)
    # the same walk serves another continuation of the same prompt
    other = ids.copy()
    other[21:] = (other[21:] + 7) % CFG.vocab_size
    want, _ = R.forward(CFG, params, jnp.asarray(other))
    got = tool.tail_logits(reference, CFG, params, walk, other, 21, positions)
    assert close(got, want[jnp.asarray(positions)], 1e-6)


def test_the_parity_tool_rehearses_and_its_limits_are_data():
    tool = _parity()
    assert tool.TAP_EVERY < pipeline_llm.TAP_EVERY == 128
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "trinity-large-preview.parity.json").read_text())[
                             "limits"]
    assert set(limits) == {"best_decode_row_rel_l2", "median_row_rel_l2",
                           "worst_row_rel_l2"}
    assert all(0 < v["limit"] < 0.5 and len(v["reason"]) > 40
               for v in limits.values())
    assert tool.main(["--workload", CELL, "--rehearse", "--seeds", "5",
                      "--degrade", "none,no_gate"]) == 0


def test_the_golden_names_a_request_and_holds_an_image():
    spec = json.loads((ROOT / "cdtbench" / "goldens"
                       / f"{CELL}.json").read_text())
    assert set(spec["request"]) == {"seed", "prompt"}
    assert spec["max_mean_abs_levels"] == 2.0 and spec["stride"] == 4
    from PIL import Image

    with Image.open(ROOT / "cdtbench" / "goldens" / f"{CELL}.png") as image:
        assert image.size == (256, 256)      # every 4th pixel of 1024²
