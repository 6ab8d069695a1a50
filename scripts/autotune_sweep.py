#!/usr/bin/env python
"""(Re)bake the attention tuning table (``ops/autotune.py``).

    python scripts/autotune_sweep.py --dry-run            # CPU: policy bake
    python scripts/autotune_sweep.py                      # TPU: timed sweep
    python scripts/autotune_sweep.py --bake               # write the
                                                          # in-repo shipped
                                                          # table
    python scripts/autotune_sweep.py --geometry h12.d128.q16384.kv16384.bf16

Default geometry set is the known model zoo
(``autotune.model_zoo_geometries``: SDXL self/cross, FLUX joint, WAN
self/cross). ``--dry-run`` writes what the dispatcher's one policy
(``ops/attention.policy_choice``) answers and works anywhere (no timing;
``--dry-run --bake`` rewrites the shipped table to the byte: it holds no
hand-kept row);
without it the sweep times every candidate on the live backend and
belongs on the TPU host. Every resolved entry is validated
(``autotune.validate_entry``) before writing; exit 1 on any error so a
bad bake can't land in a fleet image.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="deterministic policy resolution (CPU-safe); no "
                         "on-device timing")
    ap.add_argument("--bake", action="store_true",
                    help="write the in-repo shipped table "
                         "(ops/attn_table_default.json) instead of the "
                         "local overlay")
    ap.add_argument("--out", default=None,
                    help="explicit output path (overrides --bake/local)")
    ap.add_argument("--geometry", action="append", default=[],
                    help="geometry key string (h<H>.d<D>.q<Q>.kv<KV>."
                         "<dtype>); repeatable; default: the model zoo")
    ap.add_argument("--mesh", default=None,
                    help="mesh shape ('dp4xtp2', 'tp=2', 'dp=2,tp=4'): "
                         "sweep the PER-SHARD geometries a tp-sharded "
                         "site executes (heads divided by the tp degree) "
                         "instead of the full-H ones")
    ap.add_argument("--runs", type=int, default=3,
                    help="timed-mode runs per candidate")
    cli = ap.parse_args()

    from comfyui_distributed_tpu.ops import autotune

    tp = 1
    if cli.mesh:
        try:
            axes = autotune.parse_mesh_spec(cli.mesh)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        tp = axes.get("tp", 1)

    if not cli.dry_run:
        import jax

        platform = jax.devices()[0].platform
        if platform != "tpu":
            # a timed sweep off-TPU would "measure" every pallas
            # candidate as a lowering failure and bake an all-xla table
            # that silently loses the flash/fused wins fleet-wide
            print(f"error: timed sweep needs a TPU (platform={platform}); "
                  "use --dry-run for the deterministic policy bake",
                  file=sys.stderr)
            return 1

    if cli.geometry:
        try:
            geometries = [autotune.GeometryKey.from_key_str(g)
                          for g in cli.geometry]
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
    else:
        geometries = sorted(autotune.model_zoo_geometries().values())
    if tp > 1:
        sharded = sorted({g.shard(tp) for g in geometries})
        skipped = len(geometries) - len(
            [g for g in geometries if g.num_heads % tp == 0])
        if skipped:
            print(f"note: {skipped} geometry(ies) have head counts not "
                  f"divisible by tp={tp}; swept unsharded", file=sys.stderr)
        geometries = sharded

    mode = "dry" if cli.dry_run else "timed"
    errors = 0
    entries: dict[str, dict] = {}
    for key in geometries:
        entry = autotune.sweep_geometry(key, mode=mode, runs=cli.runs)
        rec = entry.to_dict()
        if entry.choice is None:
            errors += 1
            print(json.dumps({"geometry": key.key_str(), "error":
                              entry.detail or "sweep failed"}), flush=True)
            continue
        problems = autotune.validate_entry(key, entry.choice)
        if problems:
            errors += 1
            rec["legality_errors"] = problems
        print(json.dumps(rec), flush=True)
        if not problems:
            entries[key.key_str()] = entry.choice.to_dict()

    if cli.out:
        out_path = Path(cli.out)
    elif cli.bake:
        out_path = Path(autotune.__file__).parent / "attn_table_default.json"
    else:
        out_path = autotune.table_path()

    if cli.bake or cli.out:
        # full rewrite of a standalone artifact
        payload = {"version": autotune.TABLE_VERSION,
                   "mode": mode, "entries": entries}
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(payload, indent=1) + "\n")
    else:
        # merge into the live local overlay the serving dispatcher reads
        table = autotune.TuningTable(path=out_path, shipped=False)
        for ks, d in entries.items():
            table.record(autotune.GeometryKey.from_key_str(ks),
                         autotune.KernelChoice.from_dict(d, source="sweep"),
                         save=False)
        table.save()
    print(json.dumps({"written": str(out_path), "entries": len(entries),
                      "errors": errors, "mode": mode,
                      "mesh": cli.mesh or None, "tp_shards": tp}),
          flush=True)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
