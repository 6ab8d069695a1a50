"""The builder's parity check of a ``keye`` cell, on the chip:

    python -m cdtbench.parity_keye --workload <cell> [--seeds 1,2] [--degrade none,kv_fp8,...]

builds the cell's language model as the registry does (its preset, the
registry's seed), runs the SAME bound ``llm_prefill`` ``serve`` runs for the
cell's graph and an ``llm_decode`` of the same steps (the ids drawn are the
served program's), and holds the model to the float32 reference
(``cdtbench/reference/llm_keye_reference.py``, a copy of the repo's),
teacher-forced on those ids and walked layer by layer, ``REFERENCE_BLOCK``
query rows at a time. The comparison is ``parity_glm``'s, for its reason (a
selection is discrete: ``why_given_selections`` in
``reference/<config>.parity.json``):

(a) **the selections themselves**: the served functions are walked once
    more and hand back what they kept (``llm_keye.prefill_chunk`` chunk by
    chunk with ``keep_masks`` — the function ``llm_prefill`` scans — then
    ``decode_step`` on the drawn ids with ``keep_rows``; the walk's logits
    are held to the served programs' own). On every ``SAMPLE_EVERY``-th
    query of every layer the reference computes its OWN scores and top
    2048; for every position in the system's set and not in the
    reference's, or the reverse, the reference's score of it against the
    score at the set's last place, as a gap relative to the RMS of the
    query's scores.
(b) **logits with the reference GIVEN the system's selections**, at the last
    prompt position and every ``TAP_EVERY``-th decoded position through the
    caches: the precision comparison, on the logits the SERVED programs
    produced.

``--degrade`` (several, comma-separated: they share the process, the ids and
ONE walk of the reference — given the stated arm's selections, scoring every
arm's) runs the model below what the configuration states or with a piece
of its mathematics left out; each must FAIL (a) or (b) on every seed:
``kv_fp8`` (the K/V rows rounded to fp8 e4m3 wherever attention reads
them), ``index_fp8`` (the index keys rounded to fp8 wherever the scorer
reads them), ``no_relu`` (the indexer's ReLU dropped), ``no_qk_norm`` (the
per-head norms of q and k left off), ``top1024`` (1024 keys a query in
place of 2048), ``half_experts`` (experts 64–127 left out of every layer).
All are built HERE, around the served code, which has no switch for them.
``--compile-only`` compiles both programs for a described v5e instead (no
chip needed, nothing runs) and prints their memory. Not part of a measured
run.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import workload as W  # noqa: E402
from cdtbench.kinds.keye import request_sizes  # noqa: E402
from cdtbench.parity import compare, summary, verdict  # noqa: E402
from cdtbench.parity_glm import (_fp8, gap_stats, rows_of,  # noqa: E402
                                 selection_readings)
from cdtbench.parity_kimi import compile_only  # noqa: E402

HERE = Path(__file__).resolve().parent
DEGRADE = ("none", "kv_fp8", "index_fp8", "no_relu", "no_qk_norm", "top1024",
           "half_experts")
TAP_EVERY = 16            # decoded positions compared (the served taps: 128)
SAMPLE_EVERY = 64         # queries whose selections are compared
REFERENCE_BLOCK = 1024    # query rows of the reference at a time


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cdtbench_llm_keye_reference",
        HERE / "reference" / "llm_keye_reference.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def lowered(arm: str):
    """The served model as ``arm`` runs it, while its functions are traced:
    wrappers around the functions of ``ops/index_gqa_attention``,
    ``ops/index_select_attention``, ``ops/expert_share`` and
    ``models/llm_keye`` the model calls."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import llm_keye as K
    from comfyui_distributed_tpu.ops import (expert_share,
                                             index_gqa_attention as gqa_ops,
                                             index_select_attention as ops)

    patched = ((gqa_ops, "masked_chunk_gqa"), (gqa_ops, "gathered_step"),
               (gqa_ops, "index_scores"), (ops, "index_step"),
               (expert_share, "held_part_by_shape"),
               (expert_share, "held_part_token"),
               (K, "_attn_in"))
    kept = {(m, n): getattr(m, n) for m, n in patched}

    def was(module, name):
        return kept[(module, name)]

    try:
        if arm == "kv_fp8":
            gqa_ops.masked_chunk_gqa = lambda q, kv, *a, **kw: was(
                gqa_ops, "masked_chunk_gqa")(q, _fp8(kv), *a, **kw)
            gqa_ops.gathered_step = lambda q, kv, *a, **kw: was(
                gqa_ops, "gathered_step")(q, _fp8(kv), *a, **kw)
        elif arm == "index_fp8":
            gqa_ops.index_scores = lambda q, w, k, *a, **kw: was(
                gqa_ops, "index_scores")(q, w, _fp8(k), *a, **kw)
            ops.index_step = lambda q, w, k, *a, **kw: was(
                ops, "index_step")(q, w, _fp8(k), *a, **kw)
        elif arm == "no_relu":
            # Σ_j w_j (q_j · k) = (Σ_j w_j q_j) · k: one product a query
            def bare(q_i, w, k_i, dtype):
                q = jnp.einsum("tj,tjd->td", w, q_i.astype(dtype).astype(
                    jnp.float32))
                return jnp.dot(q.astype(dtype), k_i.astype(dtype).T,
                               preferred_element_type=jnp.float32) + 0.0

            gqa_ops.index_scores = lambda q, w, k, start, dtype, \
                kernel=None: bare(q, w, k, dtype)
            ops.index_step = lambda q_i, w, ki, pos, topk, dtype: \
                ops.top_rows(bare(q_i[None], w[None], ki, dtype)[0], pos,
                             topk)
        elif arm == "no_qk_norm":
            def unnormed(cfg, p, x, rope):
                # _attn_in without the two RMS norms (q then has the scale
                # of a 2048-wide product, as an un-normed head would)
                H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                           cfg.head_dim)
                T = x.shape[0]
                y = K._dot(x, p["w_in"], jnp.dtype(cfg.dtype))
                q = y[:, :H * d].reshape(T, H, d)
                k = y[:, H * d:(H + G) * d].reshape(T, G, d)
                return K._rope(q, *rope), K._rope(k, *rope), \
                    y[:, (H + G) * d:].reshape(T, G, d)

            K._attn_in = unnormed
        elif arm == "half_experts":
            def halved(name):
                def fn(x, idx, w, e_gu, *a, **kw):
                    # experts in the upper half of the held ones: left out
                    return was(expert_share, name)(
                        x, idx, jnp.where(idx < e_gu.shape[0] // 2, w, 0.0),
                        e_gu, *a, **kw)
                return fn

            expert_share.held_part_by_shape = halved("held_part_by_shape")
            expert_share.held_part_token = halved("held_part_token")
        yield
    finally:
        for (module, name), fn in kept.items():
            setattr(module, name, fn)


def cfg_of(cfg, arm: str):
    return dataclasses.replace(cfg, topk=cfg.topk // 2) \
        if arm == "top1024" else cfg


class Walk:
    """The served model's own functions, chunk by chunk and token by token,
    handing back what they kept; one an arm, compiled once."""

    def __init__(self, cfg, arm: str):
        import jax
        import jax.numpy as jnp

        from comfyui_distributed_tpu.models import llm_keye as K

        self.cfg, self.arm = cfg_of(cfg, arm), arm
        cfg = self.cfg

        def chunk(w, c, i, s, v):
            logits, cache, _, _, masks = K.prefill_chunk(
                cfg, w, c, i, s, v, keep_masks=True)
            return logits, cache, jnp.stack(
                [jnp.packbits(m.astype(jnp.uint8), axis=1) for m in masks])

        def token(w, c, t, p):
            logits, cache, _, kept = K.decode_step(cfg, w, c, t, p,
                                                   keep_rows=True)
            return logits, cache, jnp.stack([r for r, _ in kept]), \
                jnp.stack([v for _, v in kept])

        self.chunk, self.token = jax.jit(chunk), jax.jit(token)

    def __call__(self, params, ids, n_prompt: int, rows_kept):
        """Logits at the last prompt position and every decoded position
        ``[1 + new, V]``, and the selections as packed bits ``[layers,
        rows, bytes]`` of the query rows ``rows_kept`` (a bool over all
        positions: which to keep)."""
        import jax.numpy as jnp
        import numpy as np

        cfg, total = self.cfg, len(ids)
        chunk = min(cfg.prefill_chunk_tokens, n_prompt)
        n = -(-n_prompt // chunk)
        with lowered(self.arm):              # the first calls trace
            cache = cfg.model.empty_cache(cfg, max(total, n * chunk))
            width = -(-cache["kv"][0].shape[0] // chunk) * chunk
            padded = np.pad(np.asarray(ids[:n_prompt]),
                            (0, n * chunk - n_prompt))
            packed, logits_rows = [], []
            for i in range(n):
                valid = min(chunk, n_prompt - i * chunk)
                logits, cache, bits = self.chunk(
                    params, cache, jnp.asarray(
                        padded[i * chunk:(i + 1) * chunk], jnp.int32),
                    i * chunk, valid)
                keep = rows_kept[i * chunk:i * chunk + valid]
                packed.append(np.asarray(bits)[:, :valid][:, keep])
            logits_rows.append(np.asarray(logits))
            for j in range(n_prompt, total):
                logits, cache, rows, valid = self.token(
                    params, cache, jnp.asarray(ids[j], jnp.int32), j)
                logits_rows.append(np.asarray(logits))
                if rows_kept[j]:
                    rows, valid = np.asarray(rows), np.asarray(valid)
                    mask = np.zeros((rows.shape[0], width), np.uint8)
                    for layer in range(rows.shape[0]):
                        mask[layer, rows[layer][valid[layer]]] = 1
                    packed.append(np.packbits(mask, axis=1)[:, None])
        return np.stack(logits_rows), np.concatenate(packed, axis=1)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="20261002")
    parser.add_argument("--degrade", default="none")
    parser.add_argument("--rehearse", action="store_true",
                        help="the tiny preset and the rehearsal sizes (CPU)")
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args(argv)
    arms = args.degrade.split(",")
    if any(a not in DEGRADE for a in arms):
        parser.error(f"--degrade takes {DEGRADE}")
    if arms[0] != "none":
        arms = ["none"] + arms      # the reference is given the stated arm's

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.diffusion.pipeline_llm import LLMPipeline
    from comfyui_distributed_tpu.graph.nodes_builtin import rewrite_prompt_ids
    from comfyui_distributed_tpu.models.registry import PRESETS

    cell = W.assemble(args.workload, rehearsal=args.rehearse)
    cfg = PRESETS[cell.preset].llm
    n_prompt, new_tokens = request_sizes(cell)
    temperature = float(cell.graph[cell.traffic["nodes"]["prompt"][0]]
                        ["inputs"]["temperature"])
    if args.compile_only:
        return compile_only(cfg, n_prompt, new_tokens, args.topology)
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(f"[parity] needs the chip; JAX found {device.platform}",
              file=sys.stderr)
        return 3
    limits = json.loads((HERE / "reference"
                         / f"{cell.config['name']}.parity.json").read_text())
    reference = load_reference()
    block = REFERENCE_BLOCK if not args.rehearse else 16
    every = SAMPLE_EVERY if not args.rehearse else 4
    tap_every = TAP_EVERY if not args.rehearse else 4
    total = n_prompt + new_tokens
    params = cfg.model.init(cfg, jax.random.key(0))   # the registry's seed
    pipe = LLMPipeline(cfg, params)
    prefill = pipe.programs(n_prompt, new_tokens)[0]
    decode = pipe.decode_fn(n_prompt, new_tokens, tap_every=tap_every)
    taps = [i for i in range(new_tokens) if (i + 1) % tap_every == 0]
    positions = [n_prompt - 1] + [n_prompt + i for i in taps]
    picked = [0] + [1 + i for i in taps]      # rows of a walk's logits
    sampled = np.arange(total) % every == every - 1
    everything = np.ones(total, bool)
    walks = {arm: Walk(cfg, arm) for arm in arms}
    stats = gap_stats(reference, cfg.topk)          # the STATED rule's
    results, faults = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        prompt_ids = rewrite_prompt_ids(f"parity prompt of seed {seed}",
                                        n_prompt, cfg.vocab_size)
        timings = {}
        for attempt in ("first", "second"):       # the first call compiles
            t0 = time.monotonic()
            logits, cache, *_ = prefill(jnp.asarray(prompt_ids, jnp.int32))
            jax.block_until_ready(logits)
            timings[f"prefill_{attempt}"] = time.monotonic() - t0
            t0 = time.monotonic()
            out, tap_logits, _, finite = decode(
                logits, cache, jax.random.key(int(seed)),
                jnp.asarray(temperature, jnp.float32))
            jax.block_until_ready(tap_logits)
            timings[f"decode_{attempt}"] = time.monotonic() - t0
        del cache
        served = np.concatenate([np.asarray(logits)[None],
                                 np.asarray(tap_logits)])
        ids = np.concatenate([np.asarray(prompt_ids), np.asarray(out)])
        got, kept = {}, {}
        for arm in arms:
            t0 = time.monotonic()
            got[arm], kept[arm] = walks[arm](
                params, ids, n_prompt,
                everything if arm == "none" else sampled)
            timings[f"walk_{arm}"] = time.monotonic() - t0
        given_bits = kept["none"]                    # [layers, total, bytes]
        sample_at = np.flatnonzero(sampled)
        kept["none"] = given_bits[:, sampled]
        off = {arm: [] for arm in arms}
        gap = {arm: [] for arm in arms}

        def unpacked(bits):
            return jnp.unpackbits(jnp.asarray(bits), axis=1)[
                :, :total].astype(bool)

        def given(layer, lo, n):
            return unpacked(given_bits[layer, lo:lo + n])

        def tap(layer, lo, scores):
            inside = (sample_at >= lo) & (sample_at < lo + scores.shape[0])
            if not inside.any():
                return
            rows = scores[jnp.asarray(sample_at[inside] - lo)]
            for arm in arms:
                n_off, g = stats(rows, unpacked(kept[arm][layer, inside]))
                off[arm].append(np.asarray(n_off))
                gap[arm].append(np.asarray(g))

        t0 = time.monotonic()
        want = np.asarray(reference.forward(
            cfg, params, jnp.asarray(ids, jnp.int32), positions, block=block,
            given=given, tap=tap)[0])
        timings["reference"] = time.monotonic() - t0
        for arm in arms:
            # the stated precision is held on what the SERVED programs
            # produced (the walk, another compilation of the same functions,
            # gives the selections and is compared beside them); an arm
            # below it exists only as a walk
            mine = served if arm == "none" else got[arm][picked]
            result = {"seed": seed, "arm": arm, "finite": bool(finite),
                      "rows": rows_of(mine, want, positions, n_prompt),
                      "selections": selection_readings(off[arm], gap[arm])}
            if arm == "none":
                result["rows_walk"] = rows_of(got[arm][picked], want,
                                              positions, n_prompt)
                result["walk_vs_served_rel_l2"] = max(
                    compare(a, b)["rel_l2"]
                    for a, b in zip(got[arm][picked], served))
            result["faults"] = verdict(result["rows"], limits["limits"]) \
                + ([] if result["finite"] else ["a non-finite logit"])
            for stat, held in limits["selection_limits"].items():
                if not result["selections"][stat] <= held["limit"]:
                    result["faults"].append(
                        f"selection {stat} "
                        f"{result['selections'][stat]:.3e} over "
                        f"{held['limit']:g}")
            if arm == "none":
                faults += result["faults"]
            elif not result["faults"]:
                faults.append(f"seed {seed}: the arm {arm} passed")
            results.append(result)
            for name in ("rows", "rows_walk"):
                for row in result.get(name, ()):
                    print(f"[parity] seed {seed} {arm} {name} pos "
                          f"{row['position']:5d} ({row['what']}): rel_l2 "
                          f"{row['rel_l2']:.3e}  max_abs {row['max_abs']:.3e}"
                          f"  ref std {row['ref_std']:.3f}  argmax "
                          f"{'same' if row['same_argmax'] else 'differs'}")
            print(f"[parity] seed {seed} {arm}: selections "
                  f"{result['selections']} summary {summary(result['rows'])}"
                  f" faults {result['faults']}", flush=True)
        print(f"[parity] seed {seed}: seconds "
              f"{ {k: round(v, 2) for k, v in timings.items()} }", flush=True)
    out_dir = W.ROOT / "chiprun_out" / "cdtbench" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    line = {"workload": cell.name, "degrade": arms,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "sizes": {"prompt_tokens": n_prompt, "new_tokens": new_tokens,
                      "tap_every": tap_every, "sample_every": every},
            "inside_tolerances": not faults, "faults": faults,
            "readings": {f"{x['seed']}.{x['arm']}": {
                "given_selections": summary(x["rows"]),
                "walk_vs_served_rel_l2": x.get("walk_vs_served_rel_l2"),
                "selections": x["selections"],
                "faults": x["faults"]} for x in results},
            "results": results}
    (out_dir / f"parity.{'+'.join(arms)}.json").write_text(json.dumps(line))
    print(json.dumps({k: v for k, v in line.items() if k != "results"}))
    return 0 if not faults else 1


if __name__ == "__main__":
    sys.exit(main())
