"""The seventh prompt rewriter (decayed linear attention on three layers of
four, grouped-query attention over a SELECTION of key blocks on the fourth,
dense FFNs, muP scales, an untied head, no expert layer) at the tiny float32
preset, against the plain reference on seeded weights — logits, not tokens:
the whole prompt, the chunked prefill and decode through the cache on BOTH
sides of ``dense_len``, what a padded chunk owes the three kinds of cache
leaf, the shared pipeline, the nodes, the shipped graph, and the
benchmark's files, counts and readers of the cell."""

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
from comfyui_distributed_tpu.models import llm_sala as S
from comfyui_distributed_tpu.models import llm_sala_reference as R

ROOT = Path(__file__).resolve().parent.parent
# a float32 program against the float32 reference: logits of unit scale
# through 5 layers, sums of a few dozen terms each: 2e-5 measured; 2e-4
# leaves ten times that and is two orders under what one wrong block, a
# dropped decay or a missing muP scale reads (1e-2 and more)
F32_TOL = 2e-4
CFG = S.SalaConfig.tiny()
CELL = "minicpm-sala.brief64k-sdxl8"
T_DENSE, T_SPARSE, NEW = 21, 72, 6     # 27 ≤ dense_len (32) < 78


@pytest.fixture(scope="module")
def params():
    return S.init_sala(CFG, jax.random.key(0))


def _ids(n):
    return jax.random.randint(jax.random.key(n), (n,), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def sparse_ids():
    return _ids(T_SPARSE + NEW)


@pytest.fixture(scope="module")
def sparse_logits(params, sparse_ids):
    return R.forward(CFG, params, sparse_ids)


def close(a, b, tol=F32_TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


# --- the model against the reference ------------------------------------------


def test_the_tiny_preset_has_every_mechanism():
    assert CFG.sparse_layers == [3, 4] and CFG.lightning_layers == [0, 1, 2]
    assert CFG.num_attention_heads // CFG.num_key_value_heads == 3
    sel = CFG.selection
    assert (sel.per, sel.local_blocks, sel.table) == (4, 2, 5)
    assert (T_SPARSE + NEW) // sel.block_size + 1 > sel.table
    assert CFG.reads_selection(CFG.cache_rows(T_SPARSE + NEW))
    assert not CFG.reads_selection(CFG.cache_rows(T_DENSE + NEW))
    assert CFG.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert CFG.logit_divisor == 32 / 256
    full = S.SalaConfig.sala_cut()
    assert full.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert full.logit_divisor == 16 and full.selection.table == 96
    assert full.mixer_types.count(S.SPARSE) == 3
    with pytest.raises(ValueError, match="dense_len"):
        S.SalaConfig.tiny(dense_len=40)


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("T,chunk", [(T_SPARSE, 16), (T_SPARSE, 8),
                                     (T_DENSE, 16), (T_DENSE, 8)])
def test_chunked_prefill_is_the_reference_at_every_position(params, T, chunk,
                                                            kernel):
    """Both sides of ``dense_len``; a last chunk that is padded (72 = 4.5
    chunks of 16, 21 = 1.3); chunks a compressed window straddles."""
    ids = _ids(T + NEW)
    want = R.forward(CFG, params, ids, total=T + NEW)
    logits, cache, held, rows = llm_model.chunked_prefill(
        S.MODEL, CFG, params, ids[:T], T + NEW, all_logits=True,
        chunk=chunk, kernel=kernel)
    assert close(logits, want[:T])
    sparse = T == T_SPARSE
    # no expert layer; the rows' place holds the sparse kernel's steps
    assert held.shape == (0,) and rows.shape == ((3,) if sparse else (0,))
    assert cache["kc"][0].shape[1] == (128 if sparse else 0)


@pytest.mark.parametrize("T", [T_SPARSE, T_DENSE])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(params, T):
    ids = _ids(T + NEW)
    want = R.forward(CFG, params, ids, total=T + NEW)
    logits, cache, _ = S.prefill(CFG, params, ids[:T], T + NEW)
    assert close(logits, want[T - 1])
    for i in range(NEW):
        logits, cache, held = S.decode_step(CFG, params, cache, ids[T + i],
                                            T + i)
        assert close(logits, want[T + i]), i
    assert held.shape == (0,)


def test_the_dense_len_switch_is_the_requests_on_both_sides(params):
    """The SAME 21-token prompt under a request that stays within
    ``dense_len`` and one that passes it: the model and the reference
    switch together, and the two answers differ (the second reads
    tables)."""
    ids = _ids(T_DENSE)
    short = R.forward(CFG, params, ids, total=T_DENSE + NEW)
    # 21 tokens hold fewer blocks than a table: a longer REQUEST reads the
    # same rows; a longer PROMPT under it does not
    long_ids = _ids(T_SPARSE)
    as_dense = R.forward(dataclasses.replace(CFG, dense_len=96), params,
                         long_ids)
    as_sparse = R.forward(CFG, params, long_ids)
    assert not close(as_dense[-1], as_sparse[-1], 1e-3)
    got_dense = S.prefill(dataclasses.replace(CFG, dense_len=96), params,
                          long_ids, T_SPARSE)[0]
    got_sparse = S.prefill(CFG, params, long_ids, T_SPARSE)[0]
    assert close(got_dense, as_dense[-1]) and close(got_sparse, as_sparse[-1])
    assert close(S.prefill(CFG, params, ids, T_DENSE + NEW)[0], short[-1])
    assert close(S.prefill(CFG, params, ids, 40)[0], short[-1])


def test_the_reference_given_tables_holds_the_selection_fixed(
        params, sparse_ids, sparse_logits):
    keep = {}
    own = R.forward(CFG, params, sparse_ids, keep=keep)
    assert close(own, sparse_logits, 1e-6)
    assert len(keep["chosen"]) == len(keep["scores"]) == 2
    assert keep["chosen"][0].shape == (2, T_SPARSE + NEW, 10)
    assert close(R.forward(CFG, params, sparse_ids, tables=keep["chosen"]),
                 own, 1e-6)
    # other tables, other logits: all blocks for every query
    everything = [jnp.ones_like(t) for t in keep["chosen"]]
    assert not close(R.forward(CFG, params, sparse_ids, tables=everything),
                     own, 1e-3)
    # in row blocks, and a sample of the tables
    sampled = {"every": 2}
    blocked = R.forward(CFG, params, sparse_ids, block=8, keep=sampled)
    assert close(blocked, own, 1e-5)
    assert (np.asarray(sampled["chosen"][1])
            == np.asarray(keep["chosen"][1])[:, ::2]).all()


@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_the_models_tables_are_the_references(params, sparse_ids, kernel):
    """``interpret``: the prefill's scoring and sparse kernels, as on the
    chip; the decode steps score in the plain form either way."""
    keep = {}
    R.forward(CFG, params, sparse_ids, keep=keep)
    cache = S.empty_cache(CFG, 80)
    tables = None
    for i in range(5):
        valid = min(16, T_SPARSE - 16 * i)
        chunk = jnp.pad(sparse_ids[16 * i:16 * i + valid], (0, 16 - valid))
        *_, cache, _, _, kept = (None,) + S.prefill_chunk(
            CFG, params, cache, chunk, 16 * i, valid, kernel=kernel,
            keep_tables=True)
        kept = [np.asarray(t)[:, :valid] for t in kept]
        tables = kept if tables is None else [
            np.concatenate([a, t], 1) for a, t in zip(tables, kept)]
    for j in range(T_SPARSE, T_SPARSE + NEW):
        _, cache, _, kept = S.decode_step(CFG, params, cache, sparse_ids[j],
                                          j, keep_tables=True)
        tables = [np.concatenate([a, np.asarray(t)[:, None]], 1)
                  for a, t in zip(tables, kept)]
    for got, want in zip(tables, keep["chosen"]):
        # the system's blocks run on past the rows (whole lanes of slots)
        assert (got[..., :10] == np.asarray(want)).all()
        assert not got[..., 10:].any()


@pytest.mark.parametrize("n_valid", [1, 5, 8, 13])
def test_a_padded_chunk_advances_neither_the_states_nor_the_compressed_rows(
        params, sparse_ids, n_valid):
    """After 32 real tokens, a chunk of 16 of which ``n_valid`` are real:
    the states stand where token ``32 + n_valid − 1`` left them, the
    compressed buffers hold the slots those rows complete and no other —
    whatever ids pad the chunk."""
    def walked(pad_id):
        cache = S.empty_cache(CFG, 80)
        for i in range(2):
            _, cache, _, _ = S.prefill_chunk(
                CFG, params, cache, sparse_ids[16 * i:16 * i + 16], 16 * i,
                16)
        chunk = jnp.concatenate([sparse_ids[32:32 + n_valid], jnp.full(
            (16 - n_valid,), pad_id, sparse_ids.dtype)])
        return S.prefill_chunk(CFG, params, cache, chunk, 32,
                               jnp.asarray(n_valid))

    logits, cache, _, _ = walked(0)
    logits_b, cache_b, _, _ = walked(7)
    assert close(logits, logits_b, 1e-6)
    assert close(cache["state"], cache_b["state"], 1e-6)
    done = (32 + n_valid) // 2                  # slots 1 … done − 1 hold
    for kc, kc_b in zip(cache["kc"], cache_b["kc"]):
        assert (np.asarray(kc) == np.asarray(kc_b)).all()
        assert np.asarray(kc[:, 1:done]).any(-1).all()
        assert not np.asarray(kc[:, done:]).any()
    # and the next token decodes as if the prompt had ended there
    ids = sparse_ids[:32 + n_valid + 1]
    want = R.forward(CFG, params, ids, total=80)
    got = S.decode_step(CFG, params, cache, ids[-1], 32 + n_valid)[0]
    assert close(got, want[-1])


def test_compressed_rows_advance_once_every_stride_tokens_across_the_edge(
        params, sparse_ids):
    logits, cache, _ = S.prefill(CFG, params, sparse_ids[:T_SPARSE], 80)
    filled = [int(np.asarray(kc).any((0, 2)).sum()) for kc in cache["kc"]]
    assert filled == [T_SPARSE // 2 - 1] * 2     # windows whole by row 71
    for i in range(NEW):
        _, cache, _ = S.decode_step(CFG, params, cache,
                                    sparse_ids[T_SPARSE + i], T_SPARSE + i)
        now = int(np.asarray(cache["kc"][0]).any((0, 2)).sum())
        assert now == (T_SPARSE + i + 1) // 2 - 1, i


def test_a_bfloat16_run_fails_the_float32_tolerance(params, sparse_ids,
                                                    sparse_logits):
    low = dataclasses.replace(CFG, dtype="bfloat16")
    logits = S.prefill(low, params, sparse_ids[:T_SPARSE], 80)[0]
    assert not close(logits, sparse_logits[T_SPARSE - 1])
    assert close(logits, sparse_logits[T_SPARSE - 1], 0.3)


def test_a_request_past_the_rope_table_is_refused(params):
    short = dataclasses.replace(CFG, rope_positions=64)
    with pytest.raises(ValueError, match="rope"):
        S.prefill(short, params, _ids(72), 80)


# --- the pipeline, the nodes, the graph ---------------------------------------


def test_the_pipeline_serves_it_with_no_branch_on_its_name(params,
                                                           sparse_ids,
                                                           sparse_logits):
    pipe = pipeline_llm.LLMPipeline(CFG, params)
    assert pipe.model is S.MODEL
    assert pipe.prefill_plan(T_SPARSE) == (16, 5, None)
    ids = sparse_ids[:T_SPARSE]
    prefill, decode = pipe.programs(T_SPARSE, NEW)
    logits, cache, held, rows = prefill(ids)
    assert close(logits, sparse_logits[T_SPARSE - 1])
    out = pipe.generate(np.asarray(ids).tolist(), NEW, seed=3,
                        temperature=0.7)
    again = pipe.generate(np.asarray(ids).tolist(), NEW, seed=3,
                          temperature=0.7)
    assert out["ids"].tolist() == again["ids"].tolist() and out["finite"]
    assert out["prefill_chunks"] == 5 and out["prefill_form"] is None
    assert out["held_prefill"].shape == out["held_decode"].shape == (0,)
    rows = CFG.cache_rows(T_SPARSE + NEW)
    assert out["cache_bytes"] == {
        "sparse_kv": 2 * 2 * 2 * rows * 8 * 4,
        "sparse_index": 2 * 2 * CFG.cache_slots(rows) * 8 * 4,
        "linear": 3 * 4 * 8 * 8 * 4}
    assert CFG.cache_slots(rows) == 128
    # the rows' place holds the sparse kernel's steps: the CPU's lax form
    # has no grid, a dense request no sparse kernel
    assert out["rows_prefill"] == 0 and out["sparse_steps"].tolist() == [0] * 3
    dense = pipe.generate(np.asarray(ids[:T_DENSE]).tolist(), NEW, seed=3,
                          temperature=0.7)
    assert dense["cache_bytes"]["sparse_index"] == 0
    assert dense["sparse_steps"].shape == (0,)


def test_registry_kind_and_loaders():
    from comfyui_distributed_tpu.graph.nodes_builtin import (CheckpointLoader,
                                                             LLMLoader)
    from comfyui_distributed_tpu.models.registry import (PRESETS,
                                                         ModelRegistry)
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    assert PRESETS["sala-tiny"].kind == PRESETS["minicpm-sala"].kind == "llm"
    assert PRESETS["minicpm-sala"].llm == S.SalaConfig.sala_cut()
    assert PRESETS["minicpm-sala"].llm.model is S.MODEL
    assert PRESETS["sala-tiny"].llm == CFG
    registry = ModelRegistry()
    with pytest.raises(ValidationError, match="LLMLoader"):
        CheckpointLoader().execute("sala-tiny", model_registry=registry)
    (bundle,) = LLMLoader().execute("sala-tiny", model_registry=registry)
    assert bundle.kind == "llm" and registry.get("sala-tiny") is bundle


def _shipped_graph(tmp_path, seed):
    from comfyui_distributed_tpu.graph.executor import strip_meta

    graph = strip_meta(json.loads(
        (ROOT / "workflows" / "reprompt-sdxl.json").read_text()))
    graph["1"]["inputs"]["ckpt_name"] = "tiny"
    graph["8"]["inputs"]["llm_name"] = "sala-tiny"
    graph["9"]["inputs"].update(prompt_tokens=40, new_tokens=8)
    graph["4"]["inputs"].update(width=32, height=32, steps=1)
    graph["3"]["inputs"]["seed"] = seed
    graph["6"]["inputs"]["output_dir"] = str(tmp_path)
    return graph


def _brute_slot_tiles(cfg, prompt_tokens, new_tokens):
    """The scoring kernel's (query tile, slot tile) pairs of a request, a
    pair at a time: scored where the slot tile is the first or holds a
    window that is whole for a query of the tile."""
    from comfyui_distributed_tpu.ops import block_select_attention as bsa

    chunk = min(cfg.prefill_chunk_tokens, prompt_tokens)
    walked = -(-prompt_tokens // chunk) * chunk
    rows = cfg.cache_rows(max(prompt_tokens + new_tokens, walked))
    if rows <= cfg.dense_len:
        return {"scored": 0, "skipped": 0}
    Sc, st = cfg.cache_slots(rows), cfg.kernel_stride
    bq, slots = bsa.score_tiles(math.gcd(chunk, cfg.select_rows), Sc)
    scored = skipped = 0
    for first in range(0, walked, bq):
        last_pos = first + bq - 1
        for t in range(Sc // slots):
            seen = t == 0 or any(
                slot >= 1 and st * (slot + 1) <= last_pos + 1
                for slot in range(t * slots, (t + 1) * slots))
            scored, skipped = scored + seen, skipped + (not seen)
    n = len(cfg.sparse_layers) * cfg.num_key_value_heads
    return {"scored": n * scored, "skipped": n * skipped}


def test_the_shipped_graph_runs_and_the_counters_move_as_stated(tmp_path):
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.executor import (GraphExecutor,
                                                        validate_prompt)
    from comfyui_distributed_tpu.telemetry import metrics as tm

    def counters():
        return {
            "keys": {k: tm.LLM_ATTN_KEYS.labels(layers=k[0],
                                                phase=k[1]).value
                     for k in CFG.attended_keys(40, 8)},
            "blocks": {k: tm.LLM_SELECT_BLOCKS.labels(kind=k).value
                       for k in ("forced", "chosen")},
            "tiles": {k: tm.LLM_SELECT_SLOT_TILES.labels(kind=k).value
                      for k in ("scored", "skipped")},
            "slots": sum(tm.LLM_EXPERT_SLOTS.labels(where=w, phase=p).value
                         for w in ("held", "absent")
                         for p in ("prefill", "decode")),
            "chunks": tm.LLM_PREFILL_CHUNKS.labels().value}

    assert not validate_prompt(_shipped_graph(tmp_path, 1))
    executor = GraphExecutor()
    before = counters()
    texts = [executor.execute(_shipped_graph(tmp_path, seed))["9"][0]
             for seed in (11, 11, 12)]
    assert texts[0] == texts[1] != texts[2]
    assert len(texts[0].split()) == 8
    if telemetry.enabled():
        after = counters()
        assert after["slots"] == before["slots"]     # no expert layer
        assert after["chunks"] - before["chunks"] == 3 * 3
        for key, n in CFG.attended_keys(40, 8).items():
            assert after["keys"][key] - before["keys"][key] == 3 * n, key
        for kind, n in CFG.selected_blocks(40, 8).items():
            assert after["blocks"][kind] - before["blocks"][kind] == 3 * n
        # three chunks of 16 in query tiles of 8, 128 slots in one tile
        assert _brute_slot_tiles(CFG, 40, 8) == {"scored": 2 * 2 * 6,
                                                 "skipped": 0}
        for kind, n in _brute_slot_tiles(CFG, 40, 8).items():
            assert after["tiles"][kind] - before["tiles"][kind] == 3 * n
        rows = CFG.cache_rows(48)
        assert tm.LLM_CACHE_BYTES.labels(layers="sparse_index").value \
            == 2 * 2 * 128 * 8 * 4
        assert tm.LLM_CACHE_BYTES.labels(layers="linear").value \
            == 3 * 4 * 8 * 8 * 4


def _grid_steps(cfg, prompt_tokens, new_tokens):
    """Grid steps of the table-driven kernel in a request: every (chunk,
    ``select_rows`` queries, sparse layer, group, query tile) walks the
    blocks the compressed cache has slots for (whole lanes of slots: more
    than the K/V rows' blocks), rounded up to whole steps."""
    chunk = min(cfg.prefill_chunk_tokens, prompt_tokens)
    chunks = -(-prompt_tokens // chunk)
    rows = cfg.cache_rows(max(prompt_tokens + new_tokens, chunks * chunk))
    blocks = cfg.cache_slots(rows) // cfg.selection.per
    steps = -(-blocks // cfg.sparse_blocks_per_step)
    return (chunks * chunk // cfg.sparse_block_q * len(cfg.sparse_layers)
            * cfg.num_key_value_heads * steps)


@pytest.mark.parametrize("kernel,T", [("interpret", T_SPARSE), ("lax", T_SPARSE),
                                      ("interpret", T_DENSE)])
def test_a_prefill_hands_back_its_sparse_kernels_steps_by_fetch(params,
                                                                kernel, T):
    """In the place of the rows an expert layer multiplied: the grid steps
    of every sparse layer's kernel over the chunks, by how their K/V rows
    arrive — they sum to the grid (the lax form has none, a dense request
    no sparse kernel)."""
    _, _, held, steps = llm_model.chunked_prefill(
        S.MODEL, CFG, params, _ids(T), T + NEW, kernel=kernel)
    assert held.shape == (0,)
    if T == T_DENSE:
        assert steps.shape == (0,)
        return
    run, blocks, skipped = steps.tolist()
    if kernel == "lax":
        assert (run, blocks, skipped) == (0, 0, 0)
        return
    assert run + blocks + skipped == _grid_steps(CFG, T, NEW) == 5 * 2 * 2 * 2 * 16
    # every tile holds its own block: a step at least; late tiles' forced
    # blocks (the last three) are a run of two and a lone block
    assert run + blocks >= 5 * 2 * 2 * 2 and run > 0 and blocks > 0


def test_the_node_counts_the_sparse_steps_a_request(tmp_path, monkeypatch):
    """``cdt_llm_sparse_steps_total``'s three labels move by the grid's
    steps a request (the kernel in the interpreter here: on a TPU it is the
    served form)."""
    from comfyui_distributed_tpu import telemetry
    from comfyui_distributed_tpu.graph.executor import GraphExecutor
    from comfyui_distributed_tpu.ops import block_select_attention as bsa
    from comfyui_distributed_tpu.telemetry import metrics as tm

    if not telemetry.enabled():
        pytest.skip("telemetry is off")
    chunk = bsa.sparse_chunk
    monkeypatch.setattr(
        bsa, "sparse_chunk", lambda *a: chunk(*a[:-1], "interpret"))

    def counted():
        return [tm.LLM_SPARSE_STEPS.labels(fetch=f).value
                for f in bsa.STEP_FETCHES]

    graph = _shipped_graph(tmp_path, 21)
    graph["9"]["inputs"].update(prompt_tokens=41)    # a program of its own
    before = counted()
    executor = GraphExecutor()
    for seed in (21, 22):
        graph["3"]["inputs"]["seed"] = seed
        executor.execute(graph)
    moved = [a - b for a, b in zip(counted(), before)]
    assert sum(moved) == 2 * _grid_steps(CFG, 41, 8) == 2 * 3 * 2 * 2 * 2 * 16
    assert moved[0] + moved[1] >= 2 * 3 * 2 * 2 * 2 and moved[2] > 0


def test_the_rules_counts_are_a_brute_force_count():
    """``attended_keys`` and ``selected_blocks`` from the rule, a query at
    a time."""
    sel, T, new = CFG.selection, 72, 6
    rows = forced = read = 0
    for t in range(T + new):
        own = t // sel.block_size
        blocks = {0} | {b for b in range(own - sel.local_blocks + 1, own + 1)
                        if b >= 0}
        forced += len(blocks)
        n = min(own + 1, sel.table)
        read += n
        rows += (n - 1) * sel.block_size + t % sel.block_size + 1
    keys = CFG.attended_keys(T, new)
    assert keys[("sparse", "prefill")] + keys[("sparse", "decode")] \
        == 2 * rows
    assert keys[("lightning", "prefill")] == 3 * T * (T + 1) // 2
    assert CFG.selected_blocks(T, new) == {
        "forced": 2 * 2 * forced, "chosen": 2 * 2 * (read - forced)}
    # the scoring kernel's slot tiles: one tile of 128 slots, then two of
    # which the early chunks skip the second; none within dense_len
    assert CFG.scored_slot_tiles(T, new) == _brute_slot_tiles(CFG, T, new) \
        == {"scored": 2 * 2 * 10, "skipped": 0}
    tiles = CFG.scored_slot_tiles(440, 8)
    assert tiles == _brute_slot_tiles(CFG, 440, 8)
    assert tiles["scored"] + tiles["skipped"] == 2 * 2 * 56 * 2
    assert tiles["skipped"] == 2 * 2 * 32      # tiles whose last row < 257
    assert CFG.scored_slot_tiles(20, 6) == {"scored": 0, "skipped": 0}
    # a request within dense_len reads every row
    assert CFG.attended_keys(20, 6)[("sparse", "prefill")] == 2 * 210
    full = S.SalaConfig.sala_cut()
    keys = full.attended_keys(65536, 128)
    share = keys[("sparse", "prefill")] / (3 * 65536 * 65537 / 2)
    assert share == pytest.approx(0.1778, abs=1e-4)
    # the cell's request: 512 query tiles of 128 × 3 slot tiles of 1408,
    # three layers, two groups — the causal skip takes a third
    tiles = full.scored_slot_tiles(65536, 128)
    assert tiles == _brute_slot_tiles(full, 65536, 128)
    assert tiles["scored"] + tiles["skipped"] == 3 * 2 * 512 * 3
    assert tiles["skipped"] == 3 * 2 * (176 + 352)    # below slot 1408, 2816


# --- the benchmark's files ----------------------------------------------------


def test_the_configurations_file_is_the_registry_preset():
    from comfyui_distributed_tpu.models.registry import PRESETS

    held = json.loads((ROOT / "cdtbench" / "configs"
                       / "minicpm-sala.json").read_text())
    preset = PRESETS[held["preset"]]
    assert held["kind"] == "sala" and preset.kind == "llm"
    assert PRESETS[held["rehearsal_preset"]].llm == CFG
    fields = dataclasses.asdict(preset.llm)
    shared = [k for k in fields if k in held]
    assert len(shared) == len(fields) - 1            # all but ``dtype``
    for key in shared:
        got = fields[key]
        assert held[key] == (list(got) if isinstance(got, tuple) else got), \
            key
    assert held["llm"]["dtype"] == fields["dtype"]
    tree = S.init_sala(preset.llm, None, abstract=True)
    leaves = {k: v for k, v in tree.items() if k != "rope"}
    assert held["llm"]["parameters"] == S.param_count(preset.llm) \
        == sum(math.prod(a.shape)
               for a in jax.tree_util.tree_leaves(leaves)) == 3_929_972_864
    assert held["llm"]["bytes"] == sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(leaves))
    assert sum(n * (9 if "each of 9" in part else
                    3 if "each of 3 " in part else 1)
               for part, n in held["llm"]["parameters_by_part"].items()) \
        == held["llm"]["parameters"]
    assert held["llm"]["cache_bytes_at_65664_positions"] \
        == llm_model.cache_bytes(S.MODEL, preset.llm, 65536 + 128)
    assert held["reduced"] == ["num_hidden_layers", "mixer_types"] \
        == sorted(held["reduced_why"], reverse=True)
    published = held["published"]
    assert published["num_hidden_layers"] == 32 \
        and len(published["mixer_types"]) == 32
    assert [i for i, t in enumerate(published["mixer_types"])
            if t == "minicpm4"] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert held["mixer_types"] == published["mixer_types"][6:18]
    assert held["vocab_size"] == published["vocab_size"] == 73448
    # every size the issue names as assumed is listed
    listed = " ".join(held["assumed"])
    for word in ("kernel_size 32", "kernel_stride 16", "block_size 64",
                 "init_blocks 1", "window_size 2048", "topk 64",
                 "dense_len 8192", "per REQUEST", "exp(-2^(-8(h+1)/32))",
                 "per head over 128", "normed input"):
        assert word in listed, word
    sdxl = json.loads((ROOT / "cdtbench" / "configs"
                       / "sdxl-base.json").read_text())
    for part in ("unet", "vae", "context_len", "step_flops"):
        assert held[part] == sdxl[part], part
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == held["reduced"] \
        and entry["source"] == held["source"]
    catalog_path = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog_path.is_file():
        catalog = next(json.loads(line) for line in open(catalog_path)
                       if '"MiniCPM-SALA"' in line)
        assert held["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in held["reduced"]:
                assert held[key] == value, key
        assert published["mixer_types"] == catalog["config"]["mixer_types"]


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    repo = (ROOT / "comfyui_distributed_tpu" / "models"
            / "llm_sala_reference.py").read_bytes()
    copy = (ROOT / "cdtbench" / "reference"
            / "llm_sala_reference.py").read_bytes()
    assert repo == copy


def _cell(rehearsal=False):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import workload

    return workload.assemble(CELL, rehearsal=rehearsal)


SALA_METRICS = ["sala_prefill_ms", "sala_decode_ms_per_token",
                "sala_share_pct", "sala_prefill_mfu_pct",
                "sala_decode_hbm_pct", "sala_sparse_core_pct",
                "sala_sparse_core_mxu_pct", "sala_select_pct",
                "sala_lightning_pct", "sala_selected_keys_pct",
                "sala_index_cache_pct"]


def test_the_cell_assembles_with_the_briefs_sizes_and_the_units_step():
    from cdtbench.kinds.sala import request_sizes

    cell = _cell()
    assert cell.preset == "minicpm-sala" and cell.chips == 1
    assert request_sizes(cell) == (65536, 128)
    assert (cell.steps, cell.cfg, cell.step_key) == (8, 6.0, "1024x1024.b2")
    assert cell.traffic["clients"] == 1 and cell.traffic["loop"] == "closed"
    small = _cell(rehearsal=True)
    assert small.preset == "sala-tiny"
    # the rehearsal's request passes the tiny dense_len: the sparse path
    assert CFG.reads_selection(CFG.cache_rows(sum(request_sizes(small))))
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= set(SALA_METRICS) | {"denoise_ms_per_step",
                                         "peak_hbm_gib", "device_idle_pct"}
    assert not {n for n in names if n.startswith(("jamba_", "kimi_"))}
    bench = cell.bench
    ours = [m for m in bench["per_layer"] if m["name"].startswith("sala_")]
    assert [m["name"] for m in ours] == SALA_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "request_p50_s"
               for m in ours)
    first = bench["per_layer"].index(ours[0])           # appended as one run
    assert bench["per_layer"][first:first + len(ours)] == ours
    # the newest cell when PR 47 appended it; later PRs append after it
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert "minicpm-sala" in [c["name"] for c in bench["configs"]]
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    import cdtbench.workload as workload

    other = workload.assemble("ai21-jamba2-3b.brief64k-sdxl8")
    assert not {m["name"] for m in other.metrics("per_layer")} \
        & set(SALA_METRICS)


def test_the_counts_are_the_issues_arithmetic_and_the_models_own():
    from cdtbench.kinds.sala import (attention_core_flops,
                                     decode_bytes_per_token, layer_counts,
                                     prefill_flops, scored_windows,
                                     selected_rows)

    cell = _cell()
    full = S.SalaConfig.sala_cut()
    assert layer_counts(cell.config) == (9, 3)
    T, n = 65536, 65536 + 128
    keys = full.attended_keys(T, 128)
    assert 3 * selected_rows(cell.config, 0, T, n) \
        == keys[("sparse", "prefill")]
    assert 3 * selected_rows(cell.config, T, n, n) \
        == keys[("sparse", "decode")]
    core = attention_core_flops(cell.config, T, n)
    assert core == pytest.approx(18.77e12, rel=2e-3)
    dense = attention_core_flops(cell.config, T, 8192)   # as if dense
    assert dense == pytest.approx(105.6e12, rel=2e-3)
    flops = prefill_flops(cell.config, T, n)
    products = 2.0 * T * (9 * (5 * 4096 * 4096 + 3 * 4096 * 16384)
                          + 3 * (4096 * (3 * 4096 + 2 * 256)
                                 + 3 * 4096 * 16384))
    assert products == pytest.approx(436.2e12, rel=1e-3)
    scores = 3 * 32 * 2 * 128 * scored_windows(cell.config, 0, T, n)
    assert scores == pytest.approx(3.30e12, rel=5e-3)
    assert flops == pytest.approx(
        products + core + scores + 9 * 32 * T * 4 * 128 * 128
        + 2 * 73448 * 4096, rel=1e-12)
    tree = S.init_sala(full, None, abstract=True)
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for k, v in tree.items() if k not in ("rope", "embed")
                  for a in jax.tree_util.tree_leaves(v))
    sizes = llm_model.cache_bytes(S.MODEL, full, n)
    want = weights + 4096 * 2 + 2 * sizes["linear"] \
        + 3 * 2 * ((T + 64) / 16 * 128 * 2 + 2 * 96 * 64 * 128 * 2)
    got = decode_bytes_per_token(cell.config, T, 128)
    assert abs(got - want) / want < 1e-9
    assert 7.2e9 < got < 7.4e9
    # a request within dense_len reads every row and scores nothing
    assert scored_windows(cell.config, 0, 4096, 4096 + 128) == 0
    assert selected_rows(cell.config, 0, 4096, 4096 + 128) \
        == 4096 * 4097 / 2


def _snapshot(keys, seconds):
    return {
        "cdt_llm_attn_keys_total": {"series": [
            {"labels": {"layers": "sparse", "phase": "prefill"},
             "value": keys},
            {"labels": {"layers": "lightning", "phase": "prefill"},
             "value": 5 * keys}]},
        "cdt_llm_cache_bytes": {"series": [
            {"labels": {"layers": "sparse_kv"}, "value": 960.0},
            {"labels": {"layers": "sparse_index"}, "value": 30.0},
            {"labels": {"layers": "linear"}, "value": 10.0}]},
        "cdt_pipeline_execute_seconds": {"series": [
            {"labels": {"pipeline": "llm_decode"}, "sum": seconds,
             "count": 1},
            {"labels": {"pipeline": "llm_prefill"}, "sum": 3 * seconds,
             "count": 1}]}}


def test_the_cells_readers_read_the_trace_the_counters_and_the_clock():
    from cdtbench import readers
    from cdtbench.kinds import sala
    from cdtbench.kinds.sala import (attention_core_flops,
                                     decode_bytes_per_token, prefill_flops)

    cell = _cell()
    n = 65536 + 128
    causal = 3 * n * (n + 1) / 2
    ctx = {"cell": cell, "requests": 2,
           "records": [{"status": "success", "seconds": 8.0}] * 2,
           "opened": _snapshot(1.0 * causal, 1.0),
           "closed": _snapshot(1.4 * causal, 1.0 + 2 * 1.28),
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {"busy_s": 8.0,
                     "phase_seconds": {
                         "llm_decode": {"seconds": 1.6, "count": 1},
                         "llm_prefill": {"seconds": 5.0, "count": 1}},
                     "op_seconds": {"block_select_mha.1": 0.5,
                                    "block_select_mha.2": 0.3,
                                    "fusion.7": 1.0}}}
    assert readers.read("sala_decode_ms_per_token", ctx) \
        == pytest.approx(10.0)
    assert readers.read("sala_prefill_ms", ctx) == pytest.approx(3840.0)
    assert readers.read("sala_share_pct", ctx) == pytest.approx(
        100 * 4 * 2.56 / 16.0)
    assert readers.read("sala_decode_hbm_pct", ctx) == pytest.approx(
        100 * decode_bytes_per_token(cell.config, 65536, 128) / 819e9
        / (1.6 / 128), rel=1e-9)
    assert readers.read("sala_prefill_mfu_pct", ctx) == pytest.approx(
        100 * prefill_flops(cell.config, 65536, n) / 197e12 / 5.0, rel=1e-9)
    assert readers.read("sala_sparse_core_mxu_pct", ctx) == pytest.approx(
        100 * attention_core_flops(cell.config, 65536, n) / 197e12 / 0.8,
        rel=1e-9)
    assert readers.read("sala_sparse_core_pct", ctx) == pytest.approx(10.0)
    assert readers.read("sala_selected_keys_pct", ctx) == pytest.approx(20.0)
    assert readers.read("sala_index_cache_pct", ctx) == pytest.approx(3.0)
    # every share stays a share for any time the chip could take
    assert prefill_flops(cell.config, 65536, n) / 197e12 > 2.2
    assert attention_core_flops(cell.config, 65536, n) / 197e12 < 0.1
    # the named scopes: from what the trace's metadata says
    sala._scope_seconds.clear()
    assert readers.read("sala_select_pct", ctx) is None      # no profile
    for name in ("sala_decode_hbm_pct", "sala_prefill_mfu_pct",
                 "sala_sparse_core_mxu_pct", "sala_sparse_core_pct",
                 "sala_select_pct", "sala_lightning_pct"):
        assert readers.read(name, {**ctx, "trace": None}) is None, name
    bare_trace = {**ctx["trace"], "op_seconds": {"fusion.7": 1.0}}
    for name in ("sala_sparse_core_mxu_pct", "sala_sparse_core_pct"):
        assert readers.read(name, {**ctx, "trace": bare_trace}) is None
    bare = {"cdt_pipeline_execute_seconds": {"series": []}}
    for name in ("sala_decode_ms_per_token", "sala_prefill_ms",
                 "sala_share_pct", "sala_selected_keys_pct",
                 "sala_index_cache_pct"):
        assert readers.read(name, {**ctx, "opened": bare,
                                   "closed": bare}) is None, name
    import cdtbench.workload as workload

    jamba = workload.assemble("ai21-jamba2-3b.brief64k-sdxl8")
    for name in SALA_METRICS:
        if name not in ("sala_prefill_ms", "sala_sparse_core_pct",
                        "sala_index_cache_pct"):        # plain data readers
            assert readers.read(name, {**ctx, "cell": jamba}) is None, name


def test_the_scope_reader_sums_self_times_by_named_scope(monkeypatch,
                                                         tmp_path):
    from cdtbench import device_layers as dl
    from cdtbench.kinds import sala

    cell = _cell()

    def meta(name, tf_op, category=""):
        return {"name": f"%{name} = f32[] fusion()", "stats": {
            "tf_op": tf_op, "hlo_category": category}}

    plane = {"name": "/device:TPU:0", "metadata": {
        1: meta("fusion.1", "jit(llm_prefill)/while/body/cdt.llm_attn/"
                            "select/dot_general:"),
        2: meta("fusion.2", "jit(llm_prefill)/cdt.llm_attn/lightning/exp"),
        3: meta("block_select_mha.1", "jit(llm_prefill)/cdt.llm_attn/"
                                      "sparse_core/pallas_call"),
        4: meta("fusion.4", "jit(llm_prefill)/cdt.llm_shared_ffn/dot")},
        "lines": {dl.OPS_LINE: [(1, 0, 2_000_000_000),
                                (2, 2_000_000_000, 1_000_000_000),
                                (3, 3_000_000_000, 4_000_000_000),
                                (4, 7_000_000_000, 1_000_000_000)]}}
    monkeypatch.setattr(dl, "find_xplane", lambda d: tmp_path / "x.pb")
    (tmp_path / "x.pb").write_bytes(b"x")
    monkeypatch.setattr(dl, "read_space", lambda path: [plane])
    sala._scope_seconds.clear()
    ctx = {"cell": cell, "trace": {"busy_s": 0.016}}
    found = sala.scope_seconds(ctx)
    unit = found["lightning"]
    assert found == {"select": 2 * unit, "sparse_core": 4 * unit,
                     "lightning": unit} and unit > 0
    assert sala.scope_pct(ctx, "select") == pytest.approx(
        100 * 2 * unit / 0.016)
    sala._scope_seconds.clear()


def test_the_parity_tool_rehearses_and_its_reference_is_the_repos(
        capsys, monkeypatch, tmp_path):
    import sys

    sys.path.insert(0, str(ROOT))
    from cdtbench import parity_sala

    assert parity_sala.load_reference().forward.__doc__ == R.forward.__doc__
    limits = json.loads((ROOT / "cdtbench" / "reference"
                         / "minicpm-sala.parity.json").read_text())
    assert set(limits["limits"]) == {"best_decode_row_rel_l2",
                                     "median_row_rel_l2", "worst_row_rel_l2"}
    assert 0 < limits["table_gap_limit"]["limit"] < 1
    monkeypatch.setattr(parity_sala.W, "ROOT", tmp_path)
    rc = parity_sala.main(["--workload", CELL, "--rehearse", "--degrade",
                           "none,state_bf16"])
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and said["inside_tolerances"]
    readings = said["readings"]
    none = next(v for k, v in readings.items() if k.endswith(".none"))
    low = next(v for k, v in readings.items() if k.endswith(".state_bf16"))
    # float32 here: the model IS the reference given its tables, and its
    # tables are the reference's; a bfloat16 state is not
    assert none["given_tables"]["worst_row_rel_l2"] < 1e-5
    assert none["tables"]["agree_pct"] == 100.0
    assert low["given_tables"]["worst_row_rel_l2"] > 1e-4


def test_the_golden_names_a_request_and_holds_an_image():
    from PIL import Image

    spec = json.loads((ROOT / "cdtbench" / "goldens"
                       / f"{CELL}.json").read_text())
    assert spec["request"]["seed"] > 0 and spec["request"]["prompt"]
    assert spec["stride"] == 4 and spec["max_mean_abs_levels"] == 2.0
    image = np.asarray(Image.open(ROOT / "cdtbench" / "goldens"
                                  / f"{CELL}.png"))
    assert image.shape == (256, 256, 3) and image.min() < image.max()
