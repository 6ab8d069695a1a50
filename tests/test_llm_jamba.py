"""The fourth prompt rewriter (Mamba-1 selective-scan layers with an
attention layer over one shared key/value head every fourteenth, dense FFNs,
a tied head, no expert layer) at the tiny float32 preset, against the plain
reference on seeded weights: the chunked prefill at several chunk lengths
through both forms of its two kernels, decode through the cache, what a
padded chunk owes the recurrent carry, the shared-K/V kernel at the
published widths, the shared pipeline with ZERO expert layers, the nodes,
the shipped graph and the benchmark's files and readers of the cell."""

import contextlib
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.diffusion import pipeline_llm
from comfyui_distributed_tpu.models import llm_jamba as J
from comfyui_distributed_tpu.models import llm_jamba_reference as R
from comfyui_distributed_tpu.models import llm_model
from comfyui_distributed_tpu.ops import gqa_attention

ROOT = Path(__file__).resolve().parent.parent
F32_TOL = 2e-4          # float32 program against the float32 reference
CFG = J.JambaConfig.tiny()
CELL = "ai21-jamba2-3b.brief64k-sdxl8"
T = 37                  # spans chunks and blocks, and is no multiple of one


@pytest.fixture(scope="module")
def params():
    return J.init_jamba(CFG, jax.random.key(0))


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
    assert CFG.attention_layers == [7] and CFG.mamba_runs == [7, 6]
    assert CFG.num_hidden_layers == CFG.attn_layer_period == 14
    assert CFG.num_key_value_heads == 1 < CFG.num_attention_heads
    assert CFG.moe_layers == () and not hasattr(CFG, "routing")
    tree = J.init_jamba(CFG, None, abstract=True)
    assert "head" not in tree                          # the head is tied
    assert [run["ssm"]["w_in"].shape[0] for run in tree["mamba"]] == [7, 6]
    assert T % CFG.prefill_chunk_tokens and T > 2 * CFG.prefill_chunk_tokens
    with pytest.raises(ValueError, match="one shared key/value head"):
        J.JambaConfig.tiny(num_key_value_heads=2)
    full = J.JambaConfig.jamba2_3b()
    assert full.attention_layers == [7, 21] and full.mamba_runs == [7, 13, 6]
    assert (full.d_inner, full.head_dim, full.scan_layers_per_token) == (
        5120, 128, 26)
    # a depth that ends on an attention layer has an empty last run
    assert J.JambaConfig.tiny(num_hidden_layers=8).mamba_runs == [7, 0]


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("chunk", [16, 8, 10, T])
def test_chunked_prefill_is_the_reference_at_every_position(
        params, ids, full_logits, kernel, chunk):
    """Chunk length does not change the answer, nor does the form of the
    two kernels; 16, 8 and 10 leave a padded last chunk of 5, 5 and 7
    real rows."""
    logits, cache, held, rows = llm_model.chunked_prefill(
        J.MODEL, CFG, params, ids, T + 4, True, chunk, kernel=kernel)
    assert close(logits, full_logits)
    assert held.shape == rows.shape == (0,)
    assert cache["ssm"].shape == (13, CFG.d_inner, CFG.mamba_d_state)
    assert cache["conv"].shape == (13, CFG.mamba_d_conv - 1, CFG.d_inner)
    assert len(cache["k"]) == len(cache["v"]) == 1


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_padded_rows_leave_state_and_conv_tails_untouched(params, ids,
                                                          kernel):
    """The recurrent leaves after a prompt that ends inside a chunk are
    those after the same prompt walked in chunks it fills exactly."""
    _, padded, _, _ = llm_model.chunked_prefill(
        J.MODEL, CFG, params, ids, T, False, 16, kernel=kernel)
    _, exact, _, _ = llm_model.chunked_prefill(
        J.MODEL, CFG, params, ids, T, False, T, kernel="lax")
    assert close(padded["ssm"], exact["ssm"], 1e-5)
    assert close(padded["conv"], exact["conv"], 1e-5)
    assert close(padded["k"][0][:T], exact["k"][0][:T], 1e-5)
    # and they are not what the pad rows' own inputs would have left
    ran_on = J.prefill_chunk(CFG, params, J.empty_cache(CFG, 48),
                             jnp.pad(ids, (0, 11)), 0, 48, kernel="lax")[1]
    assert not close(ran_on["ssm"], exact["ssm"], 1e-3)


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("last", [1, 2, 3, 16])
def test_a_last_chunk_shorter_than_the_convolution_keeps_older_tail_rows(
        params, ids, kernel, last):
    """The tail after a chunk is read from the old tail and the K−1 rows
    of ``[u | z]`` that end at ``n_valid`` (PR 46: not from the whole u
    half): with one or two real rows in the last chunk it still holds rows
    the chunk BEFORE left: what unpadded chunks leave."""
    n = 16 + last
    _, padded, _, _ = llm_model.chunked_prefill(
        J.MODEL, CFG, params, ids[:n], n, False, 16, kernel=kernel)
    cache = J.prefill_chunk(CFG, params, J.empty_cache(CFG, n), ids[:16], 0,
                            16, kernel=kernel)[1]
    exact = J.prefill_chunk(CFG, params, cache, ids[16:n], 16, last,
                            kernel="lax")[1]
    assert close(padded["conv"], exact["conv"], 1e-5)
    assert close(padded["ssm"], exact["ssm"], 1e-5)


def test_a_chunk_continues_from_the_cache_the_chunks_before_it_left(
        params, ids, full_logits):
    cache = J.empty_cache(CFG, 48)
    for lo in (0, 16):
        logits, cache, _, _ = J.prefill_chunk(
            CFG, params, cache, ids[lo:lo + 16], lo, 16, all_logits=True)
        assert close(logits, full_logits[lo:lo + 16])
    last = jnp.pad(ids[32:], (0, 11))
    logits, cache, _, _ = J.prefill_chunk(CFG, params, cache, last, 32, 5)
    assert close(logits, full_logits[-1])


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        params, ids, full_logits):
    n = 29
    logits, cache, held = J.prefill(CFG, params, ids[:n], T)
    assert close(logits, full_logits[n - 1]) and held.shape == (0,)
    step = jax.jit(lambda c, t, p: J.decode_step(CFG, params, c, t, p))
    for pos in range(n, T):
        logits, cache, held = step(cache, ids[pos], pos)
        assert close(logits, full_logits[pos]), pos
    assert held.shape == (0,) and held.dtype == jnp.int32


def test_the_reference_in_row_blocks_is_the_reference(params, ids,
                                                      full_logits):
    assert close(R.forward(CFG, params, ids, block=10), full_logits, 1e-5)
    some = R.forward(CFG, params, ids, positions=[3, T - 1], block=16)
    assert close(some, full_logits[jnp.asarray([3, T - 1])], 1e-5)


def test_a_bfloat16_run_fails_the_float32_tolerance(params, ids, full_logits):
    low = dataclasses.replace(CFG, dtype="bfloat16")
    logits, _, _ = J.prefill(low, params, ids, T, all_logits=True)
    assert not close(logits, full_logits)
    assert close(logits, full_logits, 0.2)


# --- attention over one shared key/value head ---------------------------------


def _attention_case(key, C, S, H, d):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (C, H, d)), jax.random.normal(kk, (S, d)),
            jax.random.normal(kv, (S, d)))


def _naive(q, k, v, start, scale):
    s = np.einsum("chd,sd->chs", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * scale
    rows = start + np.arange(q.shape[0])
    s = np.where(np.arange(k.shape[0])[None, None, :] <= rows[:, None, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("chs,sd->chd", p / p.sum(-1, keepdims=True),
                     np.asarray(v, np.float64))


# (block_q, block_k) of the blocked kernel: square; a K block under four q
# blocks (at start 16 the diagonal runs INSIDE the one they share); a q
# block over four K blocks; a K block LONGER than the chunk
@pytest.mark.parametrize("kernel,block_q,block_k", [
    ("lax", 8, 8), ("interpret", 8, 8), ("interpret", 4, 16),
    ("interpret", 16, 4), ("interpret", 8, 32)])
@pytest.mark.parametrize("start", [0, 16, 40])
def test_the_shared_kv_case_is_naive_attention_at_jambas_widths(
        kernel, block_q, block_k, start):
    """20 heads of 128 over one key/value head, a chunk of 16 at three
    starts over a cache of 56 rows (padded to the K block inside): the
    moving diagonal, the clamped last block and the masked step."""
    q, k, v = _attention_case(jax.random.key(5), 16, 56, 20, 128)
    got = gqa_attention.causal_chunk(
        q, k[None], v[None], jnp.int32(start), 128 ** -0.5, jnp.float32,
        block_q, block_k, kernel=kernel)
    assert got.shape == (16, 20, 128)
    assert close(got, _naive(q, k, v, start, 128 ** -0.5), 1e-5)


def test_rows_above_the_chunk_are_never_read():
    q, k, v = _attention_case(jax.random.key(6), 8, 32, 4, 8)
    poisoned_k = k.at[16:].set(jnp.nan)
    poisoned_v = v.at[16:].set(jnp.nan)
    got = gqa_attention.causal_chunk(
        q, poisoned_k[None], poisoned_v[None], jnp.int32(8), 1.0,
        jnp.float32, 8, 8, kernel="interpret")
    assert np.isfinite(np.asarray(got)).all()
    assert close(got, _naive(q, k, v, 8, 1.0), 1e-5)


def test_the_decode_step_is_the_naive_attentions_last_row():
    q, k, v = _attention_case(jax.random.key(7), 1, 24, 4, 8)
    got = gqa_attention.step(q[0], k[None], v[None], jnp.arange(24) <= 17,
                             0.3, jnp.float32)
    assert close(got, _naive(q, k, v, 17, 0.3)[0], 1e-5)


def test_the_blocked_kernel_reports_a_tier_of_its_own(monkeypatch):
    from comfyui_distributed_tpu.ops import attention, kernel_choice

    assert "shared_kv_causal" in kernel_choice.REPORTED_TIERS
    assert "shared_kv_causal" not in kernel_choice.TIERS
    attention.reset_selections()
    attention.note_causal("shared_kv_causal", 20, 128, 4096, 66560,
                          jnp.bfloat16, 1024, 1024)
    assert "shared_kv_causal:1024/1024" in attention.selection_summary()
    attention.reset_selections()


# --- the weights and the cache ------------------------------------------------


def test_the_whole_model_counts_what_the_issue_counted():
    cfg = J.JambaConfig.jamba2_3b()
    tree = J.init_jamba(cfg, None, abstract=True)

    def count(sub):
        return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(sub))

    runs = tree["mamba"]
    assert [count(r["ssm"]) // r["norm1"].shape[0] for r in runs] \
        == [41_241_792] * 3
    assert count(runs[1]) == 13 * 104_161_472
    assert count(tree["attn"][0]) == 76_682_240
    assert count(tree["embed"]) + count(tree["final_norm"]) == 167_774_720
    assert J.param_count(cfg) == count(tree) == 3_029_337_472
    assert tree["embed"].dtype == jnp.bfloat16
    assert runs[0]["ssm"]["a_log"].dtype == jnp.float32
    # 1 KiB a token for the whole model, and a state that ignores length
    sizes = llm_model.cache_bytes(cfg.model, cfg, 65536 + 128)
    assert sizes == {"recurrent": 26 * 5120 * (16 + 3) * 4,
                     "full": 65664 * 1024}
    assert llm_model.cache_bytes(cfg.model, cfg, 1024)["recurrent"] \
        == sizes["recurrent"]


def test_the_initial_state_space_remembers(params):
    """The drawn ``A``, ``Δ`` bias and ``D`` are what the docstring says:
    ``A = −1 .. −N`` a channel, ``D`` ones."""
    p = params["mamba"][0]["ssm"]
    want = np.log(np.arange(1, CFG.mamba_d_state + 1))
    assert np.allclose(np.asarray(p["a_log"][3, 5]), want, atol=1e-6)
    assert float(p["b_dt"].max()) == float(p["b_dt"].min()) == -4.0
    assert float(p["d"].min()) == 1.0 and float(jnp.abs(p["conv_b"]).max()) > 0


# --- through the shared pipeline, registry and nodes --------------------------


def test_the_pipeline_serves_a_model_with_no_expert_layer(params, ids,
                                                          full_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is J.MODEL
    assert pipe.prefill_plan(T) == (16, 3, None)
    prefill, decode = pipe.programs(T, 8)
    assert pipe.programs(T, 8)[0] is prefill
    logits, cache, held, rows = prefill(ids)
    assert close(logits, full_logits[-1])
    assert cache["k"][0].shape[0] == 48            # three chunks of 16 rows
    text = str(jax.make_jaxpr(prefill.jitted)(pipe.params, ids))
    assert text.count("scan[") >= 3        # the chunks, and a scan a run
    out = pipe.generate(np.asarray(ids).tolist(), 8, seed=3, temperature=0.7)
    again = pipe.generate(np.asarray(ids).tolist(), 8, seed=3,
                          temperature=0.7)
    assert out["ids"].tolist() == again["ids"].tolist() and out["finite"]
    assert out["prefill_chunks"] == 3 and out["prefill_form"] is None
    assert out["rows_prefill"] == 0
    assert out["held_prefill"].shape == out["held_decode"].shape == (0,)
    per_layer = CFG.d_inner * (CFG.mamba_d_state + CFG.mamba_d_conv - 1) * 4
    assert out["cache_bytes"] == {
        "recurrent": 13 * per_layer,
        "full": 2 * (T + 8) * CFG.head_dim * 4}
    own = pipe.decode_fn(T, 8, tap_every=2)
    drawn, taps, counts, _ = own(logits, cache, jax.random.key(3),
                                 jnp.asarray(0.7, jnp.float32))
    assert np.asarray(drawn).tolist() == out["ids"].tolist()
    assert taps.shape == (4, CFG.vocab_size) and counts.shape == (0,)


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["jamba-tiny"].kind == PRESETS["ai21-jamba2-3b"].kind \
        == "llm"
    assert PRESETS["ai21-jamba2-3b"].llm == J.JambaConfig.jamba2_3b()
    assert PRESETS["ai21-jamba2-3b"].llm.model is J.MODEL
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("jamba-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("jamba-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("jamba-tiny") is bundle
    from comfyui_distributed_tpu.cluster.residency import bundle_bytes

    assert bundle_bytes(bundle) == 4 * J.param_count(CFG)


def _shipped_graph(tmp_path, seed, llm_name="jamba-tiny"):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = llm_name
    graph["9"]["inputs"].update(prompt_tokens=40, new_tokens=8)
    graph["4"]["inputs"].update(width=32, height=32, steps=1)
    graph["3"]["inputs"]["seed"] = seed
    graph["6"]["inputs"]["output_dir"] = str(tmp_path)
    return graph


def _llm_counters():
    from comfyui_distributed_tpu.telemetry import metrics as tm

    phases = ("prefill", "decode")
    return {
        "slots": sum(tm.LLM_EXPERT_SLOTS.labels(where=k, phase=p).value
                     for p in phases for k in ("held", "absent")),
        "rows": sum(tm.LLM_EXPERT_ROWS.labels(form=f).value
                    for f in ("grouped", "dense", "token")),
        "scan": {p: tm.LLM_SCAN_TOKENS.labels(phase=p).value
                 for p in phases},
        "tokens": {p: tm.LLM_TOKENS.labels(phase=p).value for p in phases},
        "chunks": tm.LLM_PREFILL_CHUNKS.labels().value}


def test_the_shipped_graph_runs_and_the_counters_move_as_stated(tmp_path):
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.executor import (GraphExecutor,
                                                        validate_prompt)
    from comfyui_distributed_tpu.telemetry import metrics as tm

    assert not validate_prompt(_shipped_graph(tmp_path, 1))
    executor = GraphExecutor()
    before = _llm_counters()
    texts = [executor.execute(_shipped_graph(tmp_path, seed))["9"][0]
             for seed in (11, 11, 12)]
    assert texts[0] == texts[1] != texts[2]
    assert len(texts[0].split()) == 8
    assert all(w[0] == "t" and 0 <= int(w[1:]) < CFG.vocab_size
               for w in texts[0].split())
    if telemetry.enabled():
        after = _llm_counters()
        # no expert layer: neither expert series moves
        assert after["slots"] == before["slots"]
        assert after["rows"] == before["rows"]
        for phase, tokens in (("prefill", 40), ("decode", 8)):
            assert after["tokens"][phase] - before["tokens"][phase] \
                == 3 * tokens
            assert after["scan"][phase] - before["scan"][phase] \
                == 3 * tokens * 13
        assert after["chunks"] - before["chunks"] == 3 * 3
        assert tm.LLM_CACHE_POSITIONS.labels().value == 48
        per_layer = CFG.d_inner * (CFG.mamba_d_state + 3) * 4
        assert tm.LLM_CACHE_BYTES.labels(layers="recurrent").value \
            == 13 * per_layer
        assert tm.LLM_CACHE_BYTES.labels(layers="full").value \
            == 2 * 48 * CFG.head_dim * 4


@pytest.mark.parametrize("llm_name,module,config", [
    ("ling-tiny", "llm_hybrid", "LLMConfig"),
    ("motif-tiny", "llm_motif", "MotifConfig"),
    ("kimi-tiny", "llm_kimi", "KimiConfig")])
def test_the_older_models_expert_counters_move_as_before(llm_name, module,
                                                         config):
    """What a model HAS is what is counted: the three with expert layers
    still move both expert series by every routed slot, and never the
    scan's."""
    import importlib

    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.nodes_builtin import (LLMLoader,
                                                             TPUPromptRewrite)
    from comfyui_distributed_tpu.models.registry import ModelRegistry

    if not telemetry.enabled():
        pytest.skip("telemetry is off")
    cfg = getattr(importlib.import_module(
        f"comfyui_distributed_tpu.models.{module}"), config).tiny()
    assert cfg.moe_layers and not hasattr(cfg, "scan_layers_per_token")
    (bundle,) = LLMLoader().execute(llm_name, model_registry=ModelRegistry())
    before = _llm_counters()
    TPUPromptRewrite().execute(bundle, "a red fox", 5, prompt_tokens=40,
                               new_tokens=8)
    after = _llm_counters()
    assert after["slots"] - before["slots"] \
        == 48 * cfg.routed_slots_per_token
    assert after["rows"] > before["rows"]
    assert after["scan"] == before["scan"]


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

    prefill = pipeline_llm.LLMPipeline(CFG, params).prefill_fn(T, 8)
    try:
        spans.span = spy
        prefill(ids)
    finally:
        spans.span = real
    assert ("pipeline_call", {"pipeline": "llm_prefill", "chunk": 16}) \
        in seen


# --- the benchmark's files ----------------------------------------------------


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "ai21-jamba2-3b.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "jamba" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    # the file's ``attn_block_q/k`` are PR 37's tile: documentation no code
    # reads, the benchmark's to correct (PERF.md §7); the served tile is
    # the preset's alone
    shared = [k for k in fields
              if k in held and not k.startswith("attn_block_")]
    assert len(shared) == len(fields) - 3 == 14   # all but those, ``dtype``
    for key in shared:
        assert held[key] == fields[key], key
    assert held["llm"]["dtype"] == fields["dtype"]
    assert held["llm"]["parameters"] == J.param_count(preset.llm) \
        == 3_029_337_472
    assert held["llm"]["bytes"] == sum(
        math.prod(a.shape) * a.dtype.itemsize for a in
        jax.tree_util.tree_leaves(J.init_jamba(preset.llm, None,
                                               abstract=True)))
    assert sum(n * (26 if "each of 26" in part else
                    2 if "each of 2 " in part else 1)
               for part, n in held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert held["llm"]["state_updates_per_token"] == 26 * 5120 * 16
    assert held["reduced"] == [] and held["reduced_why"] == {}
    assert held["num_hidden_layers"] == held["published"][
        "num_hidden_layers"] == 28
    assert held["vocab_size"] == held["published"]["vocab_size"] == 65536
    assert any("not_given" in line for line in held["assumed"])
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    assert held["trace_phases"]["llm_prefill"] == "jit_llm_prefill"
    assert held["trace_phases"]["llm_decode"] == "jit_llm_decode"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ai21-jamba2-3b")
    assert entry["reduced"] == [] and entry["source"] == held["source"]
    # every key of the catalog's config, under its key, unchanged
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"AI21-Jamba2-3B"' in line)
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            assert held[key] == value, key


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_jamba_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_jamba_reference.py").read_bytes()
    assert repo == copy


def _cell(rehearsal=False):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import workload

    return workload.assemble(CELL, rehearsal=rehearsal)


JAMBA_METRICS = {"jamba_prefill_ms", "jamba_decode_ms_per_token",
                 "jamba_share_pct", "jamba_prefill_mfu_pct",
                 "jamba_decode_hbm_pct", "jamba_ssm_pct", "jamba_scan_pct",
                 "jamba_scan_hbm_pct", "jamba_attn_core_mxu_pct"}


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench.kinds.jamba import request_sizes

    cell = _cell()
    assert cell.preset == "ai21-jamba2-3b" and cell.chips == 1
    assert request_sizes(cell) == (65536, 128)
    assert cell.graph["9"]["inputs"]["temperature"] == 0.7
    assert (cell.steps, cell.cfg, cell.step_key) == (8, 6.0, "1024x1024.b2")
    assert cell.image_hw == (1024, 1024) and cell.step_flops
    assert cell.traffic["clients"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["warmup_requests"] == 2
    assert cell.config["serve_env"] == {}
    small = _cell(rehearsal=True)
    assert small.preset == "jamba-tiny"
    assert small.graph["1"]["inputs"]["ckpt_name"] == "tiny"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= JAMBA_METRICS | {"denoise_ms_per_step", "peak_hbm_gib",
                                     "denoise_mfu_pct", "device_idle_pct"}
    assert not {n for n in names
                if n.startswith(("llm_", "motif_", "kimi_"))}
    bench = cell.bench
    ours = [m for m in bench["per_layer"] if m["name"].startswith("jamba_")]
    assert {m["name"] for m in ours} == JAMBA_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "request_p50_s"
               for m in ours)
    # appended as one block and the cell after the six before it (later
    # PRs append after both)
    first = bench["per_layer"].index(ours[0])
    assert bench["per_layer"][first:first + 9] == ours
    assert bench["workloads"][6]["name"] == CELL
    for other in ("kimi-k2.6.brief32k-sdxl8", "sdxl-base.solo30"):
        import cdtbench.workload as workload

        assert not {m["name"] for m in workload.assemble(other).metrics(
            "per_layer")} & JAMBA_METRICS


def test_the_counts_are_the_issues_arithmetic_and_the_models_leaves():
    """``prefill_flops``, ``attention_core_flops`` and ``scan_bytes`` give
    ISSUE 37's counts at 65 536 tokens; ``decode_bytes_per_token`` is
    written from the configuration's sizes and the model's own weight tree
    and cache must give the same bytes."""
    from cdtbench.kinds.jamba import (attention_core_flops,
                                      decode_bytes_per_token, layer_counts,
                                      prefill_flops, scan_bytes)

    cell = _cell()
    assert layer_counts(cell.config) == (26, 2)
    core = attention_core_flops(cell.config, 65536)
    assert core == pytest.approx(44.0e12, rel=2e-3)
    flops = prefill_flops(cell.config, 65536)
    assert flops - core == pytest.approx(374.7e12, rel=1e-3)
    # one token more is 2 x 2 858.4 M products more, and its causal row
    assert prefill_flops(cell.config, 65537) - flops == pytest.approx(
        2 * 2_858_352_640 + 2 * 20 * 65537 * 512, rel=1e-9)
    assert scan_bytes(cell.config) == 4 * (4 * 5120 + 2 * 16)
    cfg = J.JambaConfig.jamba2_3b()
    tree = J.init_jamba(cfg, None, abstract=True)
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(tree))
    sizes = llm_model.cache_bytes(cfg.model, cfg, 65536 + 64)
    want = weights + cfg.hidden_size * 2 + 2 * sizes["recurrent"] \
        + sizes["full"]
    got = decode_bytes_per_token(cell.config, 65536, 128)
    assert abs(got - want) / want < 1e-9
    assert 6.1e9 < got < 6.2e9


def _snapshot(scan_prefill, seconds):
    return {
        "cdt_llm_scan_tokens_total": {"series": [
            {"labels": {"phase": "prefill"}, "value": scan_prefill},
            {"labels": {"phase": "decode"}, "value": scan_prefill / 512}]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 3 * seconds,
             "count": 1},
            {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock():
    from cdtbench import readers
    from cdtbench.kinds.jamba import (attention_core_flops,
                                      decode_bytes_per_token, prefill_flops,
                                      scan_bytes)

    cell = _cell()
    walked = 65536 * 26
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 6.0}] * 2,
           "opened": _snapshot(7.0 * walked, 1.0),
           "closed": _snapshot(9.0 * walked, 1.0 + 2 * 1.28),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 5.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 1.2, "count": 1},
                         "llm_prefill": {"seconds": 3.5, "count": 1}},
                     "op_seconds": {"selective_scan.1": 0.4,
                                    "selective_scan.2": 0.35,
                                    "shared_kv_causal_mha.1": 0.25,
                                    "shared_kv_causal_mha.2": 0.25,
                                    "fusion.7": 1.0}}}
    assert readers.read("jamba_decode_ms_per_token", ctx) \
        == pytest.approx(10.0)
    assert readers.read("jamba_prefill_ms", ctx) == pytest.approx(3840.0)
    assert readers.read("jamba_share_pct", ctx) == pytest.approx(
        100 * 4 * 2.56 / 12.0)
    need = decode_bytes_per_token(cell.config, 65536, 128)
    assert readers.read("jamba_decode_hbm_pct", ctx) == pytest.approx(
        100 * need / 819e9 / (1.2 / 128), rel=1e-9)
    assert readers.read("jamba_prefill_mfu_pct", ctx) == pytest.approx(
        100 * prefill_flops(cell.config, 65536) / 197e12 / 3.5, rel=1e-9)
    assert readers.read("jamba_attn_core_mxu_pct", ctx) == pytest.approx(
        100 * attention_core_flops(cell.config, 65536) / 197e12 / 0.5,
        rel=1e-9)
    assert readers.read("jamba_scan_pct", ctx) == pytest.approx(15.0)
    assert readers.read("jamba_scan_hbm_pct", ctx) == pytest.approx(
        100 * scan_bytes(cell.config) * walked / 819e9 / 0.75, rel=1e-9)
    # every share stays a share for any time the chip could take: the
    # counted work over the peak is the least time there is
    assert prefill_flops(cell.config, 65536) / 197e12 > 2.0
    # no trace, a trace without the kernels, or a program without the
    # series (the parent): nothing, not zero, and nothing raised
    traced = ("jamba_decode_hbm_pct", "jamba_prefill_mfu_pct",
              "jamba_attn_core_mxu_pct", "jamba_scan_pct",
              "jamba_scan_hbm_pct", "jamba_ssm_pct")
    for name in traced:
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    for name in ("jamba_attn_core_mxu_pct", "jamba_scan_pct",
                 "jamba_scan_hbm_pct"):
        assert readers.read(name, {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": [
        {"labels": {"pipeline": "txt2img_seg"}, "sum": 9.0, "count": 1}]}}
    for name in ("jamba_decode_ms_per_token", "jamba_prefill_ms",
                 "jamba_share_pct", "jamba_scan_hbm_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    # another kind's cell reads none of them
    import cdtbench.workload as workload

    kimi = workload.assemble("kimi-k2.6.brief32k-sdxl8")
    for name in ("jamba_decode_hbm_pct", "jamba_decode_ms_per_token",
                 "jamba_share_pct", "jamba_prefill_mfu_pct",
                 "jamba_attn_core_mxu_pct", "jamba_scan_hbm_pct"):
        assert readers.read(name, {**ctx, "cell": kimi}) is None, name


@pytest.mark.parametrize("arm", ["state_bf16", "dt_bf16", "weights_fp8",
                                 "no_norms", "no_d", "no_conv_bias"])
def test_the_parity_tools_lower_arms_change_what_the_program_computes(
        params, arm):
    """The six arms that must fail on the chip are built around the
    served code: here they only have to move the logits, and leave no
    trace."""
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_jamba

    ids40 = [int(i) % CFG.vocab_size for i in range(3, 43)]
    cfg = CFG
    if arm == "weights_fp8":       # what is HELD in bfloat16 goes to fp8
        cfg = dataclasses.replace(CFG, dtype="bfloat16")
        params = J.init_jamba(cfg, jax.random.key(0))

    def run(weights, around=contextlib.nullcontext):
        with around():
            return pipeline_llm.LLMPipeline(cfg, weights).generate(
                ids40, 8, 1, 0.7)

    sound = run(params)
    if arm in ("state_bf16", "dt_bf16"):
        low = run(params, lambda: parity_jamba.scan_in_bf16(arm))
    elif arm == "no_norms":
        low = run(params, lambda: parity_jamba.without_scan_norms(CFG))
    else:
        lowered = parity_jamba.lowered_weights(params, arm)
        if arm == "weights_fp8":
            assert lowered["embed"].dtype == jnp.float8_e4m3fn
            assert lowered["mamba"][0]["ssm"]["w_in"].dtype \
                == jnp.float8_e4m3fn
            assert lowered["final_norm"].dtype == jnp.float32
        else:
            leaf = {"no_d": "d", "no_conv_bias": "conv_b"}[arm]
            assert not np.asarray(lowered["mamba"][1]["ssm"][leaf]).any()
            assert lowered["mamba"][0]["ssm"]["w_in"] \
                is params["mamba"][0]["ssm"]["w_in"]
        low = run(lowered)
    assert not close(low["prefill_logits"], sound["prefill_logits"], 1e-4)
    again = run(params)
    assert np.array_equal(np.asarray(again["prefill_logits"]),
                          np.asarray(sound["prefill_logits"]))


def test_the_parity_tool_rehearses_and_its_reference_is_the_repos(params):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_jamba

    assert parity_jamba.TAP_EVERY < pipeline_llm.TAP_EVERY == 128
    assert close(parity_jamba.load_reference().forward(
        CFG, params, jnp.arange(16)), R.forward(CFG, params, jnp.arange(16)),
        1e-6)
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "ai21-jamba2-3b.parity.json").read_text())[
                             "limits"]
    assert set(limits) == {"best_decode_row_rel_l2", "median_row_rel_l2",
                           "worst_row_rel_l2"}
    assert all(0 < v["limit"] < 0.5 and len(v["reason"]) > 40
               for v in limits.values())
    assert parity_jamba.main(["--workload", CELL, "--rehearse", "--seeds",
                              "5"]) == 0


def test_the_golden_names_a_request_and_holds_an_image():
    spec = json.loads((ROOT / "cdtbench" / "goldens"
                       / f"{CELL}.json").read_text())
    assert set(spec["request"]) == {"seed", "prompt"}
    assert spec["max_mean_abs_levels"] == 2.0 and spec["stride"] == 4
    from PIL import Image

    image = Image.open(ROOT / "cdtbench" / "goldens" / f"{CELL}.png")
    assert image.size == (256, 256)
