"""Who asked for a cell's set-up, and when: boot and the cell's warm-up
requests through ``serve`` as the benchmark drives them (``cdtbench``'s own
server, cell and warm-up; no window, no profile), then the set-up ledger by
OWNER and as a TIMELINE, read from the server before it stops:

    python scripts/setup_timeline.py --workload sdxl-base.solo30 --seed 7

- ``/distributed/metrics.json``: by phase, Σ
  ``cdt_program_build_under_seconds`` against Σ
  ``cdt_program_build_seconds``; the seconds under every owner of 1 s and
  more; the call sites of anonymous programs; ``abstract_pass_s`` and
  ``cold_compile_s`` through their data files;
- ``/distributed/trace/boot`` and ``/distributed/trace/<prompt id>`` of each
  warm-up: the ``build.*`` spans on one axis — the long ones, what the
  pool's threads held side by side, and the spans' seconds against the
  series that hold only builds of more than 0.1 s.

Everything read is written under ``chiprun_out/setup_timeline/<cell>/``.
``--rehearse`` is the CPU rehearsal (tiny presets: no time it prints is a
device's). The parent process never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cdtbench import readers, workload as W          # noqa: E402
from cdtbench.run import METRICS, Run                # noqa: E402
from cdtbench.server import census, say, serve, series  # noqa: E402

BUILD = "cdt_program_build_seconds"
UNDER = "cdt_program_build_under_seconds"
BACKEND = ("cache_key", "cache_read", "compile")
FLOOR = 0.1     # a series with nothing at or under it holds only spans


def flat(tree: list) -> list:
    out = []
    for node in tree:
        out.append(node)
        out.extend(flat(node["children"]))
    return out


def owners(metrics: dict) -> None:
    built, under = defaultdict(float), defaultdict(float)
    by_owner = defaultdict(lambda: defaultdict(float))
    for s in series(metrics, BUILD):
        built[s["labels"]["phase"]] += s["sum"]
    for s in series(metrics, UNDER):
        labels = s["labels"]
        under[labels["phase"]] += s["value"]
        by_owner[labels["under"]][labels["phase"]] += s["value"]
    say("by phase, seconds built · seconds under an owner · relative gap:")
    for phase, seconds in sorted(built.items(), key=lambda kv: -kv[1]):
        gap = abs(under[phase] - seconds) / seconds if seconds else 0.0
        say(f"  {phase:10s} {seconds:9.3f} {under[phase]:9.3f}  {gap:.1e}")
    say("seconds under every owner of 1 s and more (and under nobody):")
    for owner, phases in sorted(by_owner.items(),
                                key=lambda kv: -sum(kv[1].values())):
        whole = sum(phases.values())
        if whole >= 1.0 or owner == "-":
            say(f"  {whole:8.2f} s  {owner}: " + ", ".join(
                f"{k} {v:.2f}" for k, v in sorted(phases.items(),
                                                  key=lambda kv: -kv[1])))
    say("anonymous programs, by the line that called them:")
    for s in series(metrics, BUILD):
        labels = s["labels"]
        if "@" in labels["program"]:
            say(f"  {s['sum']:8.2f} s  {s['count']:5d}x  {labels['phase']:6s}"
                f" {labels['program']}")


def timeline(traces: dict, metrics: dict) -> None:
    spans = [dict(s, trace=name) for name, tree in traces.items()
             for s in flat(tree) if s["name"].startswith("build.")]
    spans.sort(key=lambda s: s["start"])
    if not spans:
        say("no build.* span in any trace")
        return
    t0 = min(s["start"] for name, tree in traces.items() for s in flat(tree))
    for name, tree in traces.items():
        every = flat(tree)
        say(f"trace {name}: {len(every)} spans, "
            f"{sum(s['name'].startswith('build.') for s in every)} build.*")
    say("builds of 1 s and more (start from the first span · seconds · "
        "self · thread · what):")
    for s in spans:
        a = s["attrs"]
        if s["duration_s"] >= 1.0:
            say(f"  {s['start'] - t0:8.2f} {s['duration_s']:7.2f} "
                f"{float(a.get('self_s', 0)):7.2f}  {a['thread'][:24]:24s} "
                f"{s['name'][6:]} {a['program']} {a.get('outcome', '')} "
                f"[{s['trace']}]")
    # the pools: what their threads held side by side
    for pool in (s for s in spans if s["attrs"].get("outcome") == "pooled"):
        lo, hi = pool["start"], pool["start"] + pool["duration_s"]
        inside = [s for s in spans if s is not pool and lo <= s["start"] <= hi
                  and s["attrs"]["thread"] != pool["attrs"]["thread"]]
        held = sum(s["duration_s"] for s in inside)
        say(f"pool {pool['attrs']['program']} at {lo - t0:.2f}: wall "
            f"{pool['duration_s']:.2f} s, {len(inside)} spans of "
            f"{len({s['attrs']['thread'] for s in inside})} threads, "
            f"{held:.2f} s summed = {held / pool['duration_s']:.2f} abreast")
    # the spans against the series that hold ONLY builds over FLOOR
    mine = [s for s in spans if s["attrs"].get("outcome") != "pooled"
            and not s["attrs"]["thread"].startswith("ThreadPoolExecutor")]
    spanned = defaultdict(float)
    for s in mine:
        phase = s["name"][6:]
        spanned[s["attrs"]["program"],
                "backend" if phase in BACKEND else phase] += float(
            s["attrs"]["self_s"])
    held = defaultdict(float)
    small = defaultdict(int)
    for s in series(metrics, BUILD):
        labels = s["labels"]
        if labels["phase"] == "first_run":
            continue
        key = (labels["program"], "backend" if labels["phase"] in BACKEND
               else labels["phase"])
        held[key] += s["sum"]
        small[key] += dict(map(tuple, s["buckets"]))[FLOOR]
    big = [k for k in held if not small[k] and k[0] != "draw_leaf"]
    ours, theirs = (sum(spanned[k] for k in big), sum(held[k] for k in big))
    say(f"series holding only builds over {FLOOR} s: {len(big)}; their "
        f"seconds {theirs:.3f}, the spans' {ours:.3f} "
        f"({100 * (ours - theirs) / theirs if theirs else 0:+.2f}%); all "
        f"spans {sum(spanned.values()):.2f} s of "
        f"{sum(held.values()):.2f} s built")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    cell = W.assemble(args.workload, rehearsal=args.rehearse)
    out_dir = ROOT / "chiprun_out" / "setup_timeline" / cell.name
    env = dict(cell.config.get("serve_env", {}))
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{cell.chips}")
    with serve(out_dir, env) as server:
        device = census(server)
        say(f"cell {cell.name} on {device}")
        if device["platform"] != "tpu" and not args.rehearse:
            say("no TPU: nothing a chip run would say")
            return 3
        run = Run(cell, server, out_dir, args.seed, 0.0, False)
        run.warm_up()
        metrics = server.request(METRICS)["metrics"]
        traces = {"boot": server.request("/distributed/trace/boot")["tree"]}
        for line in (out_dir / "requests.jsonl").read_text().splitlines():
            record = json.loads(line)
            traces[record["prefix"]] = server.request(
                f"/distributed/trace/{record['prompt_id']}")["tree"]
    (out_dir / "metrics.json").write_text(json.dumps(metrics))
    (out_dir / "traces.json").write_text(json.dumps(traces))
    owners(metrics)
    ctx = {"cell": cell, "opened": metrics, "closed": metrics}
    for name in ("abstract_pass_s", "cold_compile_s", "trace_s",
                 "cache_read_s", "miss_compile_s", "cache_hit_pct"):
        say(f"{name}: {readers.read(name, ctx)}")
    timeline(traces, metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())
