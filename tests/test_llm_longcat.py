"""The sixth prompt rewriter (a double layer of two latent attentions and
two dense FFNs with a shortcut expert branch beside them; a softmax router
whose outputs include identity experts) at the tiny float32 preset, against
the plain reference on seeded weights: whole, chunked and mid-chunk prefill
through both forms of the blocked attention, thirty tokens decoded through
the cache, what each piece of the layer's mathematics moves, the chip's
share of the experts tied to the uncut double layer, the counts both
programs hand back, the shared pipeline, the nodes, the shipped graph and
the benchmark's files, counts and readers of the cell."""

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
from comfyui_distributed_tpu.models import llm_longcat as L
from comfyui_distributed_tpu.models import llm_longcat_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import expert_share

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = L.LongcatConfig.tiny()
CELL = "longcat-flash-omni.brief16k-sdxl8"
T = 52                  # a prompt of 22 (mid-chunk) and 30 decoded tokens
PROMPT = 22
LAYERS = CFG.num_layers


@pytest.fixture(scope="module")
def params():
    return L.init_longcat(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (T,), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def full(params, ids):
    """The reference's logits at every position, and its counts."""
    logits, held, zero = R.forward(CFG, params, ids)
    return logits, [int(n) for n in held + zero]


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# --- prefill through the cache, decode through the cache ----------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.moe_layers == [0, 1] and CFG.model.prefill_chunk \
        is L.prefill_chunk
    assert T > 3 * CFG.prefill_chunk_tokens > PROMPT > CFG.prefill_chunk_tokens
    r = CFG.routing
    assert (r.score, r.normalised, r.zero_experts) == ("softmax", False, 8)
    assert r.outputs == 24 > CFG.router_experts > CFG.num_experts == 4
    assert expert_share.prefill_form(CFG.prefill_chunk_tokens, r,
                                     CFG.expert_tile) == "grouped"
    assert CFG.q_scale == 2.0 and CFG.kv_scale == pytest.approx(math.sqrt(2))
    cache = L.empty_cache(CFG, 8)       # two latent leaves a layer, twice
    assert len(cache["c"]) == len(cache["kr"]) == 2 * LAYERS
    assert L.BRANCH_SUBLAYER == 0


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("chunk", [16, 8, 10, T])
def test_chunked_prefill_is_the_reference_at_every_position(
        params, ids, full, chunk, kernel):
    """8 and 16 cut the prompt into chunks with a padded last one, 10
    neither divides T nor is a multiple of the attention blocks, T is the
    whole prompt as one chunk."""
    want, want_counts = full
    got, cache, counts = L.prefill(CFG, params, ids, T + 3, all_logits=True,
                                   chunk=chunk, kernel=kernel)
    assert got.shape == (T, CFG.vocab_size) and close(got, want)
    assert counts.tolist() == want_counts          # [held … | zero …]
    assert all(c.shape[0] >= T + 3 for c in cache["c"] + cache["kr"])
    last = L.prefill(CFG, params, ids, T + 3, chunk=chunk, kernel=kernel)[0]
    assert close(last, want[-1])


def test_a_chunk_continues_from_the_cache_the_chunks_before_it_left(
        params, ids, full):
    """The continuation by hand: four calls of ``prefill_chunk``, the
    cache the only thing between them; the last chunk holds 4 real rows."""
    cache = L.empty_cache(CFG, 64)
    rows, total = [], np.zeros(2 * LAYERS, np.int64)
    for start in (0, 16, 32, 48):
        n = min(16, T - start)
        chunk_ids = jnp.pad(ids[start:start + n], (0, 16 - n))
        logits, cache, counts, mult = L.prefill_chunk(
            CFG, params, cache, chunk_ids, start, n, all_logits=True)
        rows.append(logits[:n])
        assert counts.shape == (2 * LAYERS,) and mult.shape == (LAYERS,)
        assert (np.asarray(mult) >= np.asarray(counts[:LAYERS])).all()
        # padded rows count in no slot: held + zero ≤ the real rows' slots
        assert (np.asarray(counts).reshape(2, LAYERS).sum(0)
                <= n * CFG.moe_topk).all()
        total += np.asarray(counts)
    assert close(jnp.concatenate(rows), full[0])
    assert total.tolist() == full[1]


def test_a_prompt_that_ends_mid_chunk_then_thirty_tokens_through_the_cache(
        params, ids, full):
    want, want_counts = full
    logits, cache, counts = L.prefill(CFG, params, ids[:PROMPT], T)
    assert close(logits, want[PROMPT - 1])
    total = np.asarray(counts)
    for t in range(PROMPT, T):
        logits, cache, counts = L.decode_step(CFG, params, cache, ids[t], t)
        assert close(logits, want[t]), t
        total = total + np.asarray(counts)
    assert T - PROMPT == 30 and total.tolist() == want_counts


def test_the_reference_in_query_blocks_is_the_reference(params, ids, full):
    blocked, held, zero = R.forward(CFG, params, ids, positions=[T - 1, 3],
                                    block=16)
    assert close(blocked, full[0][jnp.asarray([T - 1, 3])], 1e-6)
    assert [int(n) for n in held + zero] == full[1]


def test_a_bfloat16_run_fails_the_float32_tolerance(params, ids, full):
    low = dataclasses.replace(CFG, dtype="bfloat16")
    got = L.prefill(low, params, ids, T, all_logits=True)[0]
    assert not close(got, full[0])
    assert close(got, full[0], 0.2)


# --- the layer's mathematics, piece by piece -----------------------------------


def _prefill(cfg, params, ids, around=contextlib.nullcontext):
    with around():
        return L.prefill(cfg, params, ids, T, all_logits=True)[0]


@pytest.mark.parametrize("piece", ["q scale", "latent scale", "identity part",
                                   "softmax", "not normalised",
                                   "branch from the first sublayer",
                                   "selection bias"])
def test_every_piece_of_the_mathematics_moves_the_logits(params, ids, full,
                                                         piece):
    """Each is in the served model AND the reference (they agree above);
    leaving one out of the program alone must show."""
    from cdtbench import parity_longcat as tool

    weights, around = params, contextlib.nullcontext
    if piece == "q scale":
        cfg = dataclasses.replace(CFG, mla_scale_q_lora=False)
    elif piece == "latent scale":
        cfg = tool.lowered_config(CFG, "no_kv_scale")
        assert cfg.kv_scale == 1.0 and CFG.kv_scale > 1.4
    elif piece == "identity part":
        cfg, around = CFG, lambda: tool.lowered("no_identity")
    elif piece == "softmax":
        cfg = tool.lowered_config(CFG, "sigmoid")
        assert cfg.routing.score == "sigmoid" and cfg.model is L.MODEL
    elif piece == "not normalised":
        cfg = tool.lowered_config(CFG, "normalised")
        assert cfg.routing.normalised
        assert dataclasses.asdict(cfg) == dataclasses.asdict(CFG)
    elif piece == "branch from the first sublayer":
        cfg, around = CFG, lambda: tool.lowered("branch_from_second")
    else:
        cfg = CFG
        weights = {**params, "layers": [
            {**layer, "moe": {**layer["moe"], "router_bias": jnp.zeros_like(
                layer["moe"]["router_bias"])}} for layer in params["layers"]]}
    moved = _prefill(cfg, weights, ids, around)
    assert not close(moved, full[0], 1e-3), piece
    assert L.BRANCH_SUBLAYER == 0                     # an arm leaves no trace
    assert close(_prefill(CFG, params, ids), full[0])


# --- the chip's share ----------------------------------------------------------


def _double_layer(cfg, layer, h, kernel="lax"):
    """The served double layer on a whole sequence as one chunk."""
    C = h.shape[0]
    cache = {k: list(v) for k, v in L.empty_cache(
        dataclasses.replace(cfg, num_layers=1), C).items()}
    return L._layer_chunk(cfg, layer, cache, 0, h, jnp.arange(C), 0,
                          jnp.ones((C,), bool), kernel)


def _reference_double_layer(cfg, layer, h):
    t = jnp.arange(h.shape[0])
    branch = None
    for i, sub in enumerate(layer["sub"]):
        c, k_rope = R.latents(cfg, sub, h, t)
        h, m, held, zero = R.sublayer_rows(
            cfg, sub, layer["moe"] if i == 0 else None, h, t, c, k_rope)
        if i == 0:
            branch, counts = m, (int(held), int(zero))
    return h + branch, counts


def test_the_parts_of_all_four_shares_add_up_to_the_uncut_double_layer():
    """16 real experts over 4 chips + 8 identity experts: every share
    routes over all 24 outputs and computes ITS four experts and — like
    every chip — both attentions, both dense FFNs and the identity part.
    ``out_s = common + part_s``, so the shares' outputs less three times
    what every chip computes alike (the reference's layer given NO expert)
    is the uncut layer: the common part counted once."""
    uncut = dataclasses.replace(CFG, n_routed_experts=16, first_expert=0)
    layer = L.init_longcat(uncut, jax.random.key(8))["layers"][1]
    layer = {**layer, "moe": {**layer["moe"], "router_bias":
                              layer["moe"]["router_bias"] * 2}}
    h = jax.random.normal(jax.random.key(10), (19, CFG.hidden_size))
    want, (want_held, want_zero) = _reference_double_layer(uncut, layer, h)
    nobody = dataclasses.replace(uncut, n_routed_experts=0)
    common, (none_held, zero_again) = _reference_double_layer(nobody, layer,
                                                              h)
    assert none_held == 0 and zero_again == want_zero > 0
    assert want_held + want_zero == 19 * CFG.moe_topk       # nothing absent
    total, held = -3.0 * common, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(CFG, first_expert=first)
        mine = {**layer, "moe": {**layer["moe"], **{
            k: layer["moe"][k][first:first + 4] for k in ("e_gu",
                                                          "e_down")}}}
        out, n_held, n_zero, rows = _double_layer(share, mine, h)
        assert int(n_zero) == want_zero        # every chip counts them all
        assert int(rows) >= int(n_held)
        total, held = total + out, held + int(n_held)
    assert close(total, want, 1e-4)
    assert held == want_held
    assert not close(common, want, 1e-2)       # the experts are a real part


def test_a_share_leaves_out_what_absent_experts_would_add(params, ids):
    other = dataclasses.replace(CFG, first_expert=8)
    a = L.prefill(CFG, params, ids, T)[0]
    b = L.prefill(other, params, ids, T)[0]
    assert not close(a, b)
    assert close(b, R.forward(other, params, ids)[0][-1])


def test_the_published_share_counts_what_the_issue_counted():
    cfg = L.LongcatConfig.longcat_share()
    assert L.param_count(cfg) == 3_964_789_760
    tree = L.init_longcat(cfg, None, abstract=True)
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
    assert 7.38 < held / 2**30 < 7.39
    layer = tree["layers"][0]

    def count(t):
        return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(t))

    sub = layer["sub"][0]
    assert count(sub["attn"]) == 90_570_752 + 1536 + 512
    assert count(sub["ffn"]) == 226_492_416 == 3 * 6144 * 12288
    assert layer["moe"]["w_router"].shape == (6144, 768)
    assert layer["moe"]["router_bias"].shape == (768,)
    assert layer["moe"]["e_gu"].shape == (8, 6144, 4096)
    outside = count(layer) - count(layer["moe"]["e_gu"]) \
        - count(layer["moe"]["e_down"])
    assert outside == 638_874_368                       # the issue's 638.87 M
    assert count(layer) == 940_864_256
    # the whole model, by the issue's line: 28 layers of 512 experts and
    # the whole vocabulary
    whole = 28 * (outside + 512 * 37_748_736) + 2 * 131072 * 6144 + 6144
    assert 560.6e9 < whole < 560.7e9
    assert cfg.routing == expert_share.Routing(
        512, 12, 1, 1, 6.0, score="softmax", normalised=False,
        zero_experts=256)
    assert (cfg.q_scale, cfg.kv_scale) == (2.0, math.sqrt(12))
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)
    # the draws that keep random weights a model: W_qb and W_kvb at the ONE
    # width-wide std the two scales restore unit variance from (q: 1536 / 6144
    # x 2^2 = 1; k_nope, v: 512 x 12 / 6144 = 1), the selection bias a tenth
    # of a softmax score's spread over 768 outputs
    specs = L._shapes(cfg)["layers"][0]
    attn = specs["sub"][0]["attn"]
    assert attn["w_qb"][2] == attn["w_b"][2] == ("normal",
                                                 1 / math.sqrt(6144))
    assert attn["w_a"][2] == attn["w_o"][2] == ("normal", None)   # fan-in
    assert cfg.q_lora_rank / 6144 * cfg.q_scale ** 2 == pytest.approx(1.0)
    assert cfg.kv_lora_rank / 6144 * cfg.kv_scale ** 2 == pytest.approx(1.0)
    assert specs["moe"]["router_bias"][2] == ("normal", 0.1 / 768)
    # 576 values a token a SUBLAYER: 16 640 positions x 8 in bfloat16
    sizes = llm_model.cache_bytes(cfg.model, cfg, 16384 + 256)
    assert sizes == {"full": 8 * 16640 * 576 * 2}
    # Kimi's kernel at Kimi's geometry and tile: two configurations on one
    from comfyui_distributed_tpu.models.llm_kimi import KimiConfig

    kimi = KimiConfig.kimi_share()
    for field in ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "prefill_chunk_tokens", "attn_block_q", "attn_block_k",
                  "expert_tile"):
        assert getattr(cfg, field) == getattr(kimi, field), field


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_splits_the_counts_into_held_and_zero(params, ids, full):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is L.MODEL
    assert pipeline_llm._slot_counts(CFG) == 2 * LAYERS
    prefill, decode = pipe.programs(T, 8)
    assert pipe.programs(T, 8)[0] is prefill
    logits, cache, counts, rows = prefill(ids)
    assert close(logits, full[0][-1])
    assert counts.tolist() == full[1] and rows.shape == (LAYERS,)
    assert cache["c"][0].shape[0] == 64             # four chunks of 16 rows
    text = str(jax.make_jaxpr(prefill.jitted)(pipe.params, ids))
    assert text.count("scan[") >= 1
    out = pipe.generate(np.asarray(ids).tolist(), 8, seed=3, temperature=0.7)
    again = pipe.generate(np.asarray(ids).tolist(), 8, seed=3,
                          temperature=0.7)
    assert out["ids"].tolist() == again["ids"].tolist() and out["finite"]
    assert out["prefill_chunks"] == 4 and out["prefill_form"] == "grouped"
    assert out["held_prefill"].tolist() == full[1][:LAYERS]
    assert out["zero_prefill"].tolist() == full[1][LAYERS:]
    assert out["held_decode"].shape == out["zero_decode"].shape == (LAYERS,)
    per_layer = out["held_decode"] + out["zero_decode"]
    assert (per_layer <= 8 * CFG.moe_topk).all() and out["zero_decode"].sum()
    assert out["rows_prefill"] == int(np.asarray(rows).sum()) \
        >= int(out["held_prefill"].sum())
    assert out["cache_bytes"] == {"full": 2 * LAYERS * (T + 8) * 24 * 4}
    # a tap spacing of a parity tool's own leaves the drawn ids alone
    own = pipe.decode_fn(T, 8, tap_every=2)
    drawn, taps, slots, _ = own(logits, cache, jax.random.key(3),
                                jnp.asarray(0.7, jnp.float32))
    assert np.asarray(drawn).tolist() == out["ids"].tolist()
    assert taps.shape == (4, CFG.vocab_size) and slots.shape == (2 * LAYERS,)


def test_an_older_model_hands_back_no_zero_counts():
    from comfyui_distributed_tpu.models import llm_kimi as K

    cfg = K.KimiConfig.tiny()
    pipe = pipeline_llm.LLMPipeline(cfg, K.init_kimi(cfg, jax.random.key(0)))
    out = pipe.generate(list(range(3, 40)), 4, seed=1, temperature=0.7)
    assert out["held_prefill"].shape == out["held_decode"].shape == (4,)
    assert out["zero_prefill"].shape == out["zero_decode"].shape == (0,)


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["longcat-tiny"].kind == "llm" \
        == PRESETS["longcat-flash-omni"].kind
    assert PRESETS["longcat-flash-omni"].llm == L.LongcatConfig.longcat_share()
    assert PRESETS["longcat-tiny"].llm == CFG
    assert PRESETS["longcat-flash-omni"].llm.model is L.MODEL
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("longcat-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("longcat-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("longcat-tiny") is bundle
    from comfyui_distributed_tpu.cluster.residency import bundle_bytes

    assert bundle_bytes(bundle) == 4 * L.param_count(CFG)


def _shipped_graph(tmp_path, seed, llm="longcat-tiny"):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = llm
    graph["9"]["inputs"].update(prompt_tokens=40, new_tokens=8)
    graph["4"]["inputs"].update(width=32, height=32, steps=1)
    graph["3"]["inputs"]["seed"] = seed
    graph["6"]["inputs"]["output_dir"] = str(tmp_path)
    return graph


def test_the_shipped_graph_runs_and_a_third_place_is_counted(tmp_path):
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.executor import (GraphExecutor,
                                                        validate_prompt)
    from comfyui_distributed_tpu.telemetry import metrics as tm

    assert not validate_prompt(_shipped_graph(tmp_path, 1))
    executor = GraphExecutor()

    def read():
        return {p: {k: tm.LLM_EXPERT_SLOTS.labels(where=k, phase=p).value
                    for k in ("held", "absent", "zero")}
                for p in ("prefill", "decode")}

    before = read()
    texts = [executor.execute(_shipped_graph(tmp_path, seed))["9"][0]
             for seed in (11, 11, 12)]
    assert texts[0] == texts[1] != texts[2]
    assert len(texts[0].split()) == 8
    assert all(w[0] == "t" and 0 <= int(w[1:]) < CFG.vocab_size
               for w in texts[0].split())
    if not telemetry.enabled():
        return
    after = read()
    for phase, tokens in (("prefill", 40), ("decode", 8)):
        moved = {k: after[phase][k] - before[phase][k] for k in after[phase]}
        # held + absent + zero are ALL the slots: tokens x top 6 x 2 layers
        assert sum(moved.values()) == 3 * tokens * CFG.moe_topk * LAYERS
        assert moved["zero"] > 0 and moved["absent"] > 0
        assert min(moved.values()) >= 0
    assert tm.LLM_CACHE_POSITIONS.labels().value == 48
    assert tm.LLM_CACHE_BYTES.labels(layers="full").value \
        == 2 * LAYERS * 48 * 24 * 4
    # a model whose router has no identity expert leaves ``zero`` alone
    executor.execute(_shipped_graph(tmp_path, 5, llm="kimi-tiny"))
    assert {p: v["zero"] for p, v in read().items()} \
        == {p: v["zero"] for p, v in after.items()}


# --- the benchmark's files ----------------------------------------------------


def _config():
    return json.loads((ROOT / "cdtbench" / "configs"
                       / "longcat-flash-omni.json").read_text())


def test_the_configurations_file_is_the_registry_preset_and_the_catalog_row():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = _config()
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "longcat" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    shared = [k for k in fields if k in held]
    assert len(shared) == len(fields) - 1 and "dtype" not in shared
    for key in shared:
        assert held[key] == fields[key], key
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == L.param_count(preset.llm) \
        == 3_964_789_760
    assert held["llm"]["bytes"] == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in
        jax.tree_util.tree_leaves(L.init_longcat(preset.llm, None,
                                                 abstract=True)))
    assert sum(n * (4 if "each of 4" in part else 1) for part, n in
               held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert "64 chips share each layer" in held["deployment"]
    assert held["router_experts"] == held["published"]["n_routed_experts"] \
        == 64 * held["n_routed_experts"]
    assert held["published"]["vocab_size"] == 8 * held["vocab_size"]
    assert held["published"]["num_layers"] == 28
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    assert held["trace_phases"]["llm_prefill"] == "jit_llm_prefill"
    assert held["trace_phases"]["llm_decode"] == "jit_llm_decode"
    assert set(held["reduced"]) == set(held["reduced_why"]) == {
        "num_layers", "n_routed_experts", "vocab_size", "omni_towers"}
    assumed = " ".join(held["assumed"])
    for said in ("softmax", "NOT normalised", "selection bias",
                 "double layer's order", "scales are applied", "untied head",
                 "interleaved pairs", "normal(0, 0.1/768"):
        assert said in assumed, said
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert entry["reduced"] == held["reduced"]
    assert entry["source"] == held["source"]
    assert entry["file"] == "cdtbench/configs/longcat-flash-omni.json"
    # every number of the catalog's config, under its key, but the reduced
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"LongCat-Flash-Omni"' in line)
        assert held["source"] == catalog["source_url"]
        assert len(catalog["config"]) == 23
        for key, value in catalog["config"].items():
            if key not in held["reduced"]:
                assert held[key] == value, key
        assert [k for k in catalog["config"] if k in held["reduced"]] == [
            "vocab_size", "num_layers", "n_routed_experts"]


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_longcat_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_longcat_reference.py").read_bytes()
    assert repo == copy


def _cell(rehearsal=False):
    from cdtbench import workload

    return workload.assemble(CELL, rehearsal=rehearsal)


NINE = {"longcat_prefill_ms", "longcat_decode_ms_per_token",
        "longcat_share_pct", "longcat_prefill_mfu_pct",
        "longcat_decode_hbm_pct", "longcat_attn_core_mxu_pct",
        "longcat_moe_branch_pct", "longcat_zero_slot_pct",
        "longcat_held_slot_pct"}


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench import readers, workload
    from cdtbench.kinds.longcat import request_sizes

    cell = _cell()
    assert cell.preset == "longcat-flash-omni" and cell.chips == 1
    assert request_sizes(cell) == (16384, 256)
    assert cell.graph["9"]["inputs"]["temperature"] == 0.7
    assert (cell.steps, cell.cfg, cell.step_key) == (8, 6.0, "1024x1024.b2")
    assert cell.step_flops and cell.image_hw == (1024, 1024)
    assert cell.traffic["clients"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["warmup_requests"] == 2
    assert cell.traffic["trace"]["requests"] == 1
    assert cell.config["serve_env"] == {}
    small = _cell(rehearsal=True)
    assert small.preset == "longcat-tiny"
    assert small.graph["1"]["inputs"]["ckpt_name"] == "tiny"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= NINE | {"denoise_ms_per_step", "denoise_mfu_pct",
                            "peak_hbm_gib"}
    # the other kinds' own readers stay out of this cell, and ours of theirs
    assert not {n for n in names if n.startswith((
        "llm_", "motif_", "kimi_", "jamba_", "trinity_"))}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for metric in bench["per_layer"]:
        if metric["name"] in NINE:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "request_p50_s"
            assert metric["unit"] == readers.spec_of(metric["name"])["unit"]
    for other in bench["workloads"]:
        if other["name"] != CELL:
            assert not {m["name"] for m in workload.assemble(
                other["name"]).metrics("per_layer")} & NINE
    # the decode reader is plain data: its scale is the graph's new_tokens
    assert readers.spec_of("longcat_decode_ms_per_token")["scale"] \
        == 1000.0 / request_sizes(cell)[1]


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    """``prefill_flops`` and ``attention_core_flops`` give ISSUE 44's counts
    at 16 384 tokens (128 TFLOP: 44 in the causal core, 84 in products, 0.6
    in held experts); ``decode_bytes_per_token`` is written from the
    configuration's sizes and the model's own weight tree must give the
    same bytes (the issue's 5.5 GB)."""
    from cdtbench.kinds.longcat import (attention_core_flops,
                                        decode_bytes_per_token, prefill_flops)

    config = _config()
    core = attention_core_flops(config, 16384)
    assert core == pytest.approx(8 * 64 * (16384 * 16385 / 2) * 640)
    assert core == pytest.approx(43.98e12, rel=1e-3)
    even = 16384 * 12 * 4 * 8 / 768               # held slots, routing even
    assert even == 8192
    whole = prefill_flops(config, 16384, even)
    assert whole == pytest.approx(128.3e12, rel=2e-3)
    experts = 2 * even * 3 * 6144 * 2048
    assert experts == pytest.approx(0.62e12, rel=1e-2)
    assert whole - core - experts == pytest.approx(83.7e12, rel=2e-3)
    # a slot more is one row of one expert more
    assert prefill_flops(config, 16384, even + 1) - whole == pytest.approx(
        2 * 3 * 6144 * 2048)
    cfg = L.LongcatConfig.longcat_share()
    tree = L.init_longcat(cfg, None, abstract=True)
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
    cache = 8 * (16384 + 128) * 576 * 2
    share = 8 / 768                                # of ALL slots, even
    want = fixed + cache + share * 12 * expert
    got = decode_bytes_per_token(config, share, 16384, 256)
    assert abs(got - want) / want < 1e-6
    assert 5.45e9 < got < 5.55e9                   # the issue's 5.5 GB


def _snapshot(held, absent, zero, seconds):
    def slots(where, phase, value):
        return {"labels": {"where": where, "phase": phase}, "value": value}

    return {
        "cdt_llm_expert_slots_total": {"series": [
            slots("held", "decode", held), slots("absent", "decode", absent),
            slots("zero", "decode", zero),
            slots("held", "prefill", 64 * held),
            slots("absent", "prefill", 64 * absent),
            slots("zero", "prefill", 64 * zero)]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 0.5 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_nine_readers_read_a_recorded_window(monkeypatch):
    from cdtbench import device_layers, readers, workload
    from cdtbench.kinds.longcat import (attention_core_flops,
                                        decode_bytes_per_token, prefill_flops)

    cell = _cell()
    config = cell.config
    slots = 2 * 256 * 48                       # two requests' decode slots
    held, zero = slots // 96, slots // 3
    absent = slots - held - zero
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 4.2}] * 2,
           "opened": _snapshot(10, 90, 50, 1.0),
           "closed": _snapshot(10 + held, 90 + absent, 50 + zero,
                               1.0 + 2 * 2.048),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 4.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 2.0, "count": 1},
                         "llm_prefill": {"seconds": 1.25, "count": 1}},
                     "op_seconds": {"latent_causal_mha.1": 0.3,
                                    "latent_causal_mha.2": 0.2,
                                    "fusion.7": 1.0}}}
    assert readers.read("longcat_decode_ms_per_token", ctx) \
        == pytest.approx(8.0)
    assert readers.read("longcat_prefill_ms", ctx) == pytest.approx(1024.0)
    assert readers.read("longcat_share_pct", ctx) == pytest.approx(
        100 * 2 * 3.072 / 8.4)
    need = decode_bytes_per_token(config, held / slots, 16384, 256)
    assert readers.read("longcat_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / (2.0 / 256), rel=1e-6)
    flops = prefill_flops(config, 16384, 64 * held / 2)
    assert readers.read("longcat_prefill_mfu_pct", ctx) == pytest.approx(
        100 * flops / 197e12 / 1.25, rel=1e-6)
    assert readers.read("longcat_attn_core_mxu_pct", ctx) == pytest.approx(
        100 * attention_core_flops(config, 16384) / 197e12 / 0.5, rel=1e-6)
    assert readers.read("longcat_zero_slot_pct", ctx) == pytest.approx(
        100 / 3)
    assert readers.read("longcat_held_slot_pct", ctx) == pytest.approx(
        100 * held / (held + absent))
    # the branch's device scopes, from the traced run's report
    report = {"phases": {
        "llm_prefill": {"llm_attn": {"seconds": 0.6},
                        "llm_router": {"seconds": 0.02},
                        "llm_experts": {"seconds": 0.03},
                        "llm_shared_ffn": {"seconds": 0.6}},
        "llm_decode": {"llm_attn": {"seconds": 0.7},
                       "llm_experts": {"seconds": 0.05},
                       "llm_shared_ffn": {"seconds": 1.0}}}}
    monkeypatch.setattr(device_layers, "of_run", lambda c: report)
    assert readers.read("longcat_moe_branch_pct", ctx) == pytest.approx(
        100 * 0.10 / 3.0)
    monkeypatch.setattr(device_layers, "of_run", lambda c: None)
    assert readers.read("longcat_moe_branch_pct", ctx) is None
    for name in NINE:                     # none may read over 100
        value = readers.read(name, ctx)
        assert value is None or name.endswith(("_ms", "per_token")) \
            or 0 <= value <= 100, name
    # no trace, a trace without the kernel, or a program without the
    # series: nothing, not zero
    for name in ("longcat_decode_hbm_pct", "longcat_prefill_mfu_pct",
                 "longcat_attn_core_mxu_pct"):
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    assert readers.read("longcat_attn_core_mxu_pct",
                        {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("longcat_decode_hbm_pct", "longcat_decode_ms_per_token",
                 "longcat_prefill_ms", "longcat_held_slot_pct",
                 "longcat_zero_slot_pct", "longcat_share_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of the four that ask the kind
    kimi = workload.assemble("kimi-k2.6.brief32k-sdxl8")
    for name in ("longcat_decode_hbm_pct", "longcat_share_pct",
                 "longcat_prefill_mfu_pct", "longcat_attn_core_mxu_pct"):
        assert readers.read(name, {**ctx, "cell": kimi}) is None, name


# --- the parity tool ------------------------------------------------------------


def test_the_walked_reference_is_the_reference(params, ids, full):
    """The tool walks the prompt once and evaluates only the rows it
    compares, against the kept keys and their own."""
    from cdtbench import parity_longcat as tool

    reference = tool.load_reference()
    walk = tool.prompt_walk(reference, CFG, params,
                            np.asarray(ids[:PROMPT]).tolist(), block=8)
    assert len(walk) == 2 * LAYERS
    assert walk[0][0].shape == (PROMPT, CFG.kv_lora_rank)
    assert walk[0][1].shape == (PROMPT, CFG.qk_rope_head_dim)
    positions = [PROMPT - 1, PROMPT + 3, T - 1]
    got = tool.tail_logits(reference, CFG, params, walk, np.asarray(ids),
                           PROMPT, positions)
    assert close(got, full[0][jnp.asarray(positions)], 1e-5)
    assert close(reference.forward(CFG, params, ids[:16])[0],
                 R.forward(CFG, params, ids[:16])[0], 1e-6)


@pytest.mark.parametrize("arm", ["cache_fp8", "weights_fp8", "no_identity",
                                 "normalised", "sigmoid",
                                 "branch_from_second", "no_kv_scale"])
def test_the_parity_tools_arms_change_what_the_program_computes(params, arm):
    """The arms that must fail on the chip are built around the served
    code: here they only have to move the logits of BOTH programs' path,
    and leave no trace."""
    from cdtbench import parity_longcat as tool

    ids40 = [int(i) % CFG.vocab_size for i in range(3, 43)]

    def run(arm):
        pipe = pipeline_llm.LLMPipeline(tool.lowered_config(CFG, arm),
                                        tool.lowered_weights(params, arm))
        with tool.lowered(arm):
            return pipe.generate(ids40, 8, 1, 0.7)

    sound, low = run("none"), run(arm)
    assert arm in tool.DEGRADE and low["finite"]
    assert not close(low["prefill_logits"], sound["prefill_logits"], 1e-4)
    again = run("none")
    assert np.array_equal(np.asarray(again["prefill_logits"]),
                          np.asarray(sound["prefill_logits"]))
    assert again["ids"].tolist() == sound["ids"].tolist()


def test_the_parity_tools_limits_are_data_with_reasons():
    from cdtbench import parity_kimi, parity_longcat as tool

    assert tool.TAP_EVERY == parity_kimi.TAP_EVERY < pipeline_llm.TAP_EVERY
    assert set(tool.LOWER) | set(tool.LEFT_OUT) | {"none"} \
        == set(tool.DEGRADE) and len(tool.DEGRADE) == 8
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "longcat-flash-omni.parity.json").read_text())
    assert set(limits["limits"]) == {
        "best_decode_row_rel_l2", "median_row_rel_l2", "worst_row_rel_l2"}
    assert all(v["limit"] > 0 and len(v["reason"]) > 40
               for v in limits["limits"].values())


def test_the_golden_names_a_request_and_holds_an_image():
    spec = json.loads((ROOT / "cdtbench" / "goldens"
                       / f"{CELL}.json").read_text())
    assert set(spec["request"]) == {"seed", "prompt"}
    assert spec["max_mean_abs_levels"] == 2.0 and spec["stride"] == 4
    from PIL import Image

    image = Image.open(ROOT / "cdtbench" / "goldens" / f"{CELL}.png")
    assert image.size == (256, 256)
