"""The ``glm`` kind's prefill counts follow the tokens the program says it
ran in the TRACED request: a request that prefilled ``[first, T)`` of its
prompt (a prefix kept between asks) is counted over that range, whatever
the rest of the window held, and one that prefilled everything reads what
the counts read before they took a range. The old counts are written out
here as they stood (PR 51–58), so that the ``first`` = 0 case is held to
them to the digit."""

import numpy as np
import pytest

from cdtbench import readers, workload
from cdtbench.kinds import glm

T, NEW, CHUNK = 65536, 128, 4096
PEAK = 197e12
CELLS = ["glm-5.brief64k-sdxl8"]
SHARES = ("glm_prefill_mfu_pct", "glm_index_mxu_pct",
          "glm_sparse_core_mxu_pct")


def _old_index_score_flops(config, prompt_tokens):
    pairs = prompt_tokens * (prompt_tokens + 1) / 2.0
    return float(config["num_hidden_layers"] * pairs * 2
                 * config["index_n_heads"] * config["index_head_dim"])


def _old_prefill_flops(config, prompt_tokens, pairs, held_slots):
    T_, D = prompt_tokens, config["hidden_size"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    expert = 3 * D * config["moe_intermediate_size"]
    total = layers * 2.0 * T_ * (glm.attention_params(config)
                                 + glm.indexer_params(config))
    total += _old_index_score_flops(config, T_)
    total += glm.selected_pair_flops(config, pairs, absorbed=True)
    total += dense * 2.0 * T_ * 3 * D * config["intermediate_size"]
    total += (layers - dense) * 2.0 * T_ * (D * config["router_experts"]
                                            + expert)
    total += 2.0 * held_slots * expert
    total += 2.0 * config["vocab_size"] * D
    return float(total)


def _snapshot(config, requests, first, prefill_s, tokens=True):
    """What ``/distributed/metrics.json`` holds after ``requests`` requests
    that each prefilled ``[first, T)`` and decoded ``NEW`` tokens: the
    pairs by the model's rule, an even router (1/32 of the slots held)."""
    layers = config["num_hidden_layers"]
    moe = layers - config["first_k_dense_replace"]
    slots = config["num_experts_per_tok"] * moe
    ran = T - first

    def slot(where, phase, value):
        return {"labels": {"where": where, "phase": phase},
                "value": requests * value}

    found = {
        "cdt_llm_attn_keys_total": {"series": [
            {"labels": {"layers": "sparse", "phase": "prefill"},
             "value": requests * layers * glm.selected_pairs(config, first,
                                                             T)},
            {"labels": {"layers": "sparse", "phase": "decode"},
             "value": requests * layers * glm.selected_pairs(config, T,
                                                             T + NEW)}]},
        "cdt_llm_expert_slots_total": {"series": [
            slot("held", "prefill", ran * slots / 32),
            slot("absent", "prefill", ran * slots * 31 / 32),
            slot("held", "decode", NEW * slots / 32),
            slot("absent", "decode", NEW * slots * 31 / 32)]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_prefill"},
             "sum": requests * prefill_s, "count": requests},
            {"labels": {"pipeline": "llm_decode"}, "sum": requests * 0.64,
             "count": requests}]}}
    if tokens:
        found["cdt_llm_tokens_total"] = {"series": [
            {"labels": {"phase": "prefill"}, "value": requests * ran},
            {"labels": {"phase": "decode"}, "value": requests * NEW}]}
    return found


def _ctx(name, first, share=1.0, tokens=True):
    """A window of three requests after two warm-ups; the traced
    ``llm_prefill`` and its kernels take ``share`` of a whole prefill's
    seconds — but the score kernel, whose work is the causal pairs of the
    queries it runs: the LAST chunk of sixteen holds 12.1% of them, not a
    sixteenth, and a kernel cannot run them in less than its own rate
    allows."""
    cell = workload.assemble(name)
    scores = glm.causal_pairs(first, T) / glm.causal_pairs(0, T)

    def snap(requests):
        return _snapshot(cell.config, requests, first, 8.0 * share, tokens)

    return {"cell": cell, "requests": 3,
            "records": [{"status": "success", "seconds": 12.0}] * 3,
            "opened": snap(2), "closed": snap(5),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "trace": {"busy_s": 10.0,
                      "phase_seconds": {
                          "llm_decode": {"seconds": 0.6, "count": 1},
                          "llm_prefill": {"seconds": 8.0 * share,
                                          "count": 1}},
                      "op_seconds": {"index_score_sums.1": 0.5 * scores,
                                     "index_score_sums.2": 0.3 * scores,
                                     "index_select_keep.5": 0.4 * share,
                                     "index_masked_mha.3": 5.0 * share,
                                     "fusion.7": 1.0 * share}}}


@pytest.mark.parametrize("name", CELLS)
def test_a_window_that_prefilled_everything_reads_the_old_counts(name):
    """``first`` = 0 — every cell until a program keeps a prefix — equals
    the counts as they stood, exactly."""
    ctx = _ctx(name, 0)
    config = ctx["cell"].config
    assert glm.prefilled_from(ctx) == 0
    layers = config["num_hidden_layers"]
    pairs = layers * glm.selected_pairs(config, 0, T)
    held = T * config["num_experts_per_tok"] * (
        layers - config["first_k_dense_replace"]) / 32
    assert glm.index_score_flops(config, T) \
        == glm.index_score_flops(config, T, 0) \
        == _old_index_score_flops(config, T)
    assert glm.prefill_flops(config, T, pairs, held) \
        == glm.prefill_flops(config, T, pairs, held, 0) \
        == _old_prefill_flops(config, T, pairs, held)
    assert readers.read("glm_prefill_mfu_pct", ctx) \
        == 100.0 * _old_prefill_flops(config, T, pairs, held) / PEAK / 8.0
    assert readers.read("glm_index_mxu_pct", ctx) \
        == 100.0 * _old_index_score_flops(config, T) / PEAK / 0.8
    assert readers.read("glm_sparse_core_mxu_pct", ctx) \
        == 100.0 * glm.selected_pair_flops(config, pairs) / PEAK / 5.0
    total = T + NEW
    seen = layers * glm.selected_pairs(config, 0, total)
    assert readers.read("glm_selected_keys_pct", ctx) \
        == 100.0 * 3 * seen / 3 / (layers * total * (total + 1) / 2.0)
    assert readers.read("glm_selected_keys_pct", ctx) == pytest.approx(
        6.14, abs=0.01)


@pytest.mark.parametrize("name", CELLS)
def test_a_kept_prefix_is_counted_over_the_tokens_the_program_ran(name):
    """4096 of 65 536 tokens prefilled a request, the traced program a
    sixteenth of the seconds: every share of the peak under 100 and equal
    to the hand count over [61440, 65536)."""
    first = T - CHUNK
    ctx = _ctx(name, first, share=1 / 16)
    config = ctx["cell"].config
    assert glm.prefilled_from(ctx) == first
    layers, H = config["num_hidden_layers"], config["num_attention_heads"]
    topk = config["index_topk"]
    # every query of the last chunk lies past index_topk keys: exactly
    # index_topk pairs a head a layer a query
    pairs = layers * CHUNK * topk
    assert layers * glm.selected_pairs(config, first, T) == pairs
    # the causal pairs of the chunk's queries: t + 1 keys at position t
    causal = int((np.arange(first, T, dtype=np.int64) + 1).sum())
    assert glm.causal_pairs(first, T) == causal
    scores = layers * causal * 2 * config["index_n_heads"] \
        * config["index_head_dim"]
    assert glm.index_score_flops(config, T, first) == scores
    D = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    expert = 3 * D * config["moe_intermediate_size"]
    held = CHUNK * config["num_experts_per_tok"] * (layers - dense) / 32
    by_hand = (
        layers * 2.0 * CHUNK * (glm.attention_params(config)
                                + glm.indexer_params(config))
        + scores
        + pairs * H * 2 * (2 * config["kv_lora_rank"]
                           + config["qk_rope_head_dim"])
        + dense * 2.0 * CHUNK * 3 * D * config["intermediate_size"]
        + (layers - dense) * 2.0 * CHUNK * (D * config["router_experts"]
                                            + expert)
        + 2.0 * held * expert + 2.0 * config["vocab_size"] * D)
    assert glm.prefill_flops(config, T, pairs, held, first) \
        == pytest.approx(by_hand, rel=1e-12)
    want = {
        "glm_prefill_mfu_pct": 100.0 * by_hand / PEAK / (8.0 / 16),
        "glm_index_mxu_pct": 100.0 * scores / PEAK / (
            0.8 * causal / glm.causal_pairs(0, T)),
        "glm_sparse_core_mxu_pct": 100.0 * pairs * H * 2 * (
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
            + config["v_head_dim"]) / PEAK / (5.0 / 16)}
    for metric in SHARES:
        got = readers.read(metric, ctx)
        assert got == pytest.approx(want[metric], rel=1e-9), metric
        assert 0 < got < 100, (metric, got)
    # the same queries' causal pairs under the program's count of the keys
    # it attended: 2048 of a mean ~63.5k keys a query
    seen = pairs + layers * glm.selected_pairs(config, T, T + NEW)
    assert readers.read("glm_selected_keys_pct", ctx) == pytest.approx(
        100.0 * seen / (layers * glm.causal_pairs(first, T + NEW)),
        rel=1e-12)
    assert 3.0 < readers.read("glm_selected_keys_pct", ctx) < 3.5
    # what the count as it stood would have said of this window: the whole
    # prompt's operations over a sixteenth of the seconds
    whole = _ctx(name, first, share=1 / 16, tokens=False)
    assert glm.prefilled_from(whole) == 0
    assert readers.read("glm_index_mxu_pct", whole) > 100
    assert readers.read("glm_sparse_core_mxu_pct", whole) \
        == pytest.approx(16 * 100.0 * glm.selected_pair_flops(
            config, layers * glm.selected_pairs(config, 0, T)) / PEAK / 5.0)


def _traced(ctx, first, before=4):
    """The two snapshots ``run.py`` takes around ONE traced request that
    prefilled ``[first, T)``, after ``before`` requests that each prefilled
    the whole prompt."""
    config = ctx["cell"].config
    opened = _snapshot(config, before, 0, 8.0)
    one = _snapshot(config, 1, first, 8.0)
    closed = {name: {"series": [
        {**a, **{k: a[k] + b[k] for k in ("value", "sum", "count")
                 if k in a}}
        for a, b in zip(group["series"], one[name]["series"])]}
        for name, group in opened.items()}
    return {"opened": opened, "closed": closed, "requests": 1}


def test_a_first_ask_in_the_window_does_not_reach_a_traced_repeat():
    """A session's window: first asks (whole prefills) beside repeats that
    kept 15 chunks. The window's MEAN tokens a request is no whole chunk
    count; the traced request's own counters give its range, and every
    share of the peak equals the all-repeats window's."""
    first = T - CHUNK
    alike = _ctx(CELLS[0], first, share=1 / 16)
    mixed = _ctx(CELLS[0], first, share=1 / 16)
    config = mixed["cell"].config
    mixed["opened"] = _snapshot(config, 2, 0, 8.0)
    mixed["closed"] = _snapshot(config, 5, 0, 8.0)   # a window of first asks
    mixed["traced"] = _traced(mixed, first)
    assert glm.traced_moved(mixed, glm.TOKENS, {"phase": "^prefill$"}) \
        == CHUNK
    assert glm.prefilled_from(mixed) == first
    for metric in SHARES + ("glm_selected_keys_pct",):
        got = readers.read(metric, mixed)
        assert got == pytest.approx(readers.read(metric, alike), rel=1e-12)
        assert 0 < got < 100, (metric, got)
    # and a traced FIRST ask in a window of repeats is a whole prefill
    whole = _ctx(CELLS[0], 0)
    whole["opened"] = _snapshot(config, 2, first, 0.5)
    whole["closed"] = _snapshot(config, 5, first, 0.5)
    whole["traced"] = _traced(whole, 0)
    assert glm.prefilled_from(whole) == 0
    assert readers.read("glm_index_mxu_pct", whole) \
        == 100.0 * _old_index_score_flops(config, T) / PEAK / 0.8


@pytest.mark.parametrize("moved_by, said", [
    (T + 0.5, "moved 65536.5 a traced request"),  # not whole tokens
    (T + CHUNK, "moved 69632 a traced request"),  # more than the prompt
    (0, "moved 0 a traced request")])             # the series stood still
def test_a_count_that_cannot_be_read_gives_no_share_of_a_peak(
        capsys, moved_by, said):
    ctx = _ctx(CELLS[0], 0)
    ctx["traced"] = _traced(ctx, 0)
    tokens = ctx["traced"]["closed"]["cdt_llm_tokens_total"]["series"][0]
    tokens["value"] = tokens["value"] - T + moved_by
    assert glm.prefilled_from(ctx) is None
    assert said in capsys.readouterr().out
    for metric in SHARES + ("glm_selected_keys_pct",):
        assert readers.read(metric, ctx) is None, metric
    # a traced run whose profile never closed: nothing, and no window's mean
    ctx["traced"] = None
    assert glm.prefilled_from(ctx) is None
    for metric in SHARES + ("glm_selected_keys_pct",):
        assert readers.read(metric, ctx) is None, metric
    assert readers.read("glm_prefill_ms", ctx) is not None


def test_a_program_without_the_series_is_counted_whole_and_said(capsys):
    ctx = _ctx(CELLS[0], 0, tokens=False)
    assert glm.prefilled_from(ctx) == 0
    assert "no cdt_llm_tokens_total series" in capsys.readouterr().out
    assert readers.read("glm_index_mxu_pct", ctx) \
        == 100.0 * _old_index_score_flops(ctx["cell"].config, T) / PEAK / 0.8
