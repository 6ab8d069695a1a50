#!/usr/bin/env python3
"""The builder's tool for the runs that set a bound: one cell, several
runs in ONE call, each a new process as the driver makes them.

    python3 cdtbench/measure.py --workload <name> --traces 0,1,0,0 \
        --seeds 2147483659,1073741827,1999999973,1500000001

Keeps every run's output and small files under
``chiprun_out/cdtbench_runs/<workload>/<i>/``, appends the result lines to
``chiprun_out/cdtbench_runs/summary.jsonl`` and prints, for each
end-to-end metric, the values and their spread (quartile distance over
the median, where there are enough runs). Never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import stats  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
RUNS = ROOT / "chiprun_out" / "cdtbench_runs"
KEEP = ("serve.log", "requests.jsonl", "trace_inspect.txt", "post.json",
        "golden_candidate.png", "metrics_close.json", "memory_stats.json")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--traces", required=True)
    parser.add_argument("--seconds", default=None)
    parser.add_argument("--tag", default="")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    traces = [int(t) for t in args.traces.split(",")]
    results = []
    for i, (seed, trace) in enumerate(zip(seeds, traces)):
        keep = RUNS / args.workload / f"{args.tag}{i}"
        shutil.rmtree(keep, ignore_errors=True)
        keep.mkdir(parents=True)
        command = [sys.executable, str(ROOT / "cdtbench" / "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--trace", str(trace)]
        if args.seconds is not None:
            command += ["--seconds", args.seconds]
        t0 = time.monotonic()
        done = subprocess.run(command, cwd=ROOT, capture_output=True,
                              text=True)
        wall = time.monotonic() - t0
        (keep / "stdout.txt").write_text(done.stdout)
        (keep / "stderr.txt").write_text(done.stderr[-20000:])
        out_dir = ROOT / "chiprun_out" / "cdtbench" / args.workload
        for name in KEEP:
            if (out_dir / name).is_file():
                shutil.copy(out_dir / name, keep / name)
        last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() \
            else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        record = {"workload": args.workload, "seed": seed, "trace": trace,
                  "rc": done.returncode, "process_s": wall, "line": line}
        results.append(record)
        with open(RUNS / "summary.jsonl", "a") as f:
            f.write(json.dumps(record) + "\n")
        print(f"--- run {i}: seed {seed} trace {trace} rc {done.returncode} "
              f"process {wall:.1f} s", flush=True)
        for text in done.stdout.splitlines():
            if text.startswith("[cdtbench]") and (
                    "window" in text or "set-up" in text or "FAULT" in text
                    or "warm-up" in text or "peak" in text
                    or "traced" in text or "post:" in text
                    or "golden" in text):
                print(text[:600], flush=True)
        print(last[:6000] if line is not None
              else "NO RESULT LINE; stderr ends:\n" + done.stderr[-2500:],
              flush=True)
        if line is None:
            print("stopping: a run without a result is not worth repeating",
                  flush=True)
            break
    by_metric: dict = {}
    for record in results:
        if record["line"] and not record["trace"]:
            for name, m in record["line"]["metrics"].items():
                by_metric.setdefault(name, []).append(m["value"])
    for name, values in by_metric.items():
        text = ", ".join(f"{v:.6g}" for v in values)
        spread = f"{100 * stats.spread(values):.3f}%" if len(values) >= 2 \
            else "n/a"
        print(f"{name}: {text}; spread {spread}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
