#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 cdtbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts ONE ``serve`` child (this process never imports JAX: the chip is
the server's), warms up the cell's own graph, measures for ``--seconds``
from the client's side of HTTP, checks every image, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``).
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else goes on earlier lines
or under ``chiprun_out/cdtbench/<workload>/``.

Without a TPU of the cell's chip count the run ends non-zero and prints no
result. ``--rehearse`` is the CPU rehearsal: tiny presets, every phase, a
last line with ``"correct": false``, no metric values, a non-zero exit.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import subprocess
import sys
import time
from pathlib import Path

T_START = time.monotonic()

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench import golden, readers, stats, traffic as T  # noqa: E402
from cdtbench import workload as W  # noqa: E402
from cdtbench.server import (BenchFailure, ROOT, census, read_image,  # noqa: E402
                             run_request, say, serve)

EXIT_INCORRECT, EXIT_FAILED, EXIT_NO_DEVICE = 1, 2, 3
METRICS = "/distributed/metrics.json"


class NoDevice(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# --- end-to-end metrics: what the client saw ---------------------------------


def _times(ctx):
    return [r["seconds"] for r in ctx["records"] if r["status"] == "success"]


END_TO_END = {
    "request_p50_s": lambda ctx: stats.percentile(_times(ctx), 50),
    "images_per_s": lambda ctx: ctx["images"] / ctx["span_s"],
    "setup_s": lambda ctx: ctx["setup_s"],
}


# --- one run -----------------------------------------------------------------


class Run:
    def __init__(self, cell: W.Cell, server, out_dir: Path, seed: int,
                 seconds: float, trace: bool):
        self.cell, self.server, self.out_dir = cell, server, out_dir
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.tracing = None            # wall times of profile start/stop
        self.requests_log = open(out_dir / "requests.jsonl", "w")

    def do_request(self, index, seed: int, prompt: str, prefix=None) -> dict:
        prefix = prefix or f"w{index:05d}"
        traffic = self.cell.traffic
        record = run_request(
            self.server, self.cell.request_graph(seed, prompt, prefix),
            timeout=float(traffic.get("request_timeout_s", 900)),
            poll_s=float(traffic.get("poll_s", 0.01)))
        record.update(index=index, seed=seed, prefix=prefix)
        self.requests_log.write(json.dumps(record) + "\n")
        self.requests_log.flush()
        return record

    def warm_up(self) -> None:
        """The cell's own graph, twice: the first compiles, the second
        confirms the steady time. No other shape is warmed. Seeds of
        their own, so that no window request repeats one; the last is the
        cell's golden request (``golden.py``), the same in every run."""
        rng = random.Random(self.seed ^ 0x5EED)
        n = int(self.cell.traffic.get("warmup_requests", 2))
        fixed = (golden.spec_of(self.cell.name) or {}).get("request")
        for i in range(n):
            seed, prompt = (rng.randrange(1, 2**31 - 1),
                            f"warm-up {i} {rng.random()}")
            if fixed and i == n - 1 and not self.cell.rehearsal:
                seed, prompt = int(fixed["seed"]), fixed["prompt"]
            record = self.do_request(-1 - i, seed, prompt,
                                     prefix=f"warm{i}")
            say(f"warm-up {i + 1}: {record['seconds']:.2f} s, "
                f"{record['status']}")
            if record["status"] != "success":
                raise BenchFailure(
                    f"warm-up request ended {record['status']!r}: "
                    f"{record['error']}\n{self.server.log_tail()}")

    def _trace_hook(self, index: int) -> None:
        plan = self.cell.traffic.get("trace", {})
        first = int(plan.get("skip_requests", 0))
        last = first + int(plan.get("requests", 1))
        if self.tracing is None and index == first:
            opened = self.server.request(METRICS)["metrics"]
            t0 = time.time()
            self.server.request("/distributed/profile/start",
                                {"out": "trace"})
            self.tracing = {"start_wall": (t0, time.time()), "first": index,
                            "opened": opened}
        elif self.tracing is not None and "stop_wall" not in self.tracing \
                and index >= last:
            t0 = time.time()
            self.server.request("/distributed/profile/stop", {},
                                timeout=300.0)
            self.tracing.update(stop_wall=(t0, time.time()),
                                requests=index - self.tracing["first"],
                                closed=self.server.request(METRICS)["metrics"])

    def window(self) -> list[dict]:
        traffic = self.cell.traffic
        stream = T.request_stream(self.seed)
        if traffic["loop"] == "closed":
            return T.run_closed(
                self.do_request, stream, self.seconds,
                clients=int(traffic.get("clients", 1)),
                on_index=self._trace_hook if self.trace else None)
        if traffic["loop"] == "open":
            due = T.arrival_schedule(self.seed, float(traffic["rate"]),
                                     self.seconds,
                                     int(traffic.get("burst", 1)))
            records = T.run_open(self.do_request, stream, due,
                                 int(traffic.get("max_in_flight", 64)))
            say(f"generator lateness: {T.lateness(records)}")
            return records
        raise BenchFailure(f"unknown loop kind {traffic['loop']!r}")


def check_images(cell: W.Cell, out_dir: Path, records) -> list[str]:
    """Every saved image of every successful request, held to what a
    generated image must be; two seeds (and two chips of one request)
    must differ. Answers the faults found."""
    import numpy as np

    faults, first, compared = [], None, False
    height, width = cell.image_hw
    for record in records:
        if record["status"] != "success":
            continue
        try:
            images = [read_image(
                out_dir / "output" / f"{record['prefix']}_{k:05d}.png",
                height, width) for k in range(cell.images_per_request)]
        except BenchFailure as e:
            faults.append(str(e))
            record["status"] = "bad_image"
            continue
        if len(images) > 1 and np.array_equal(images[0], images[1]):
            faults.append(f"request {record['index']}: chips 0 and 1 drew "
                          "the same image")
        if first is None:
            first = (record["index"], images[0])
        elif not compared:             # one comparison is enough
            compared = True
            if np.array_equal(first[1], images[0]):
                faults.append(f"requests {first[0]} and {record['index']} "
                              "gave the same image from different seeds")
    return faults


def check_golden(cell: W.Cell, out_dir: Path) -> list[str]:
    """The last warm-up request's image against the cell's golden."""
    if cell.rehearsal:
        say("rehearsal: no golden at the tiny presets, none compared")
        return []
    last = int(cell.traffic.get("warmup_requests", 2)) - 1
    height, width = cell.image_hw
    try:
        image = read_image(out_dir / "output" / f"warm{last}_00000.png",
                           height, width)
    except BenchFailure as e:
        return [f"golden request: {e}"]
    return golden.check(cell.name, image, out_dir, say)


def reduce_trace(cell: W.Cell, out_dir: Path, window_s: float | None
                 ) -> dict:
    """The trace reduction, after the serve child has exited: the only
    code of the benchmark that imports JAX, in a subprocess held to the
    CPU, never in this process."""
    (out_dir / "post_request.json").write_text(json.dumps({
        "trace_dir": str(out_dir / "profile"),
        "inspect_path": str(out_dir / "trace_inspect.txt"),
        "phases": cell.config.get("trace_phases", {}),
        "window_s": window_s}))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    try:
        done = subprocess.run(
            [sys.executable, "-m", "cdtbench.post",
             str(out_dir / "post_request.json"), str(out_dir / "post.json")],
            cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True, timeout=300)
    except subprocess.TimeoutExpired:      # run() has killed and reaped it
        raise BenchFailure("the trace reduction did not end") from None
    if done.returncode != 0:
        raise BenchFailure(f"the trace reduction failed:\n"
                           f"{done.stderr[-3000:]}")
    return json.loads((out_dir / "post.json").read_text())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at the tiny presets: every "
                             "phase, no result that counts")
    args = parser.parse_args(argv)

    try:
        cell = W.assemble(args.workload, rehearsal=args.rehearse)
    except (OSError, KeyError, ValueError) as e:
        print(f"[cdtbench] cannot assemble {args.workload!r}: {e}",
              file=sys.stderr)
        return EXIT_FAILED
    seconds = float(args.seconds if args.seconds is not None
                    else cell.bench["run_seconds"])
    out_dir = ROOT / "chiprun_out" / "cdtbench" / cell.name
    env = dict(cell.config.get("serve_env", {}))
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{cell.chips}")
    say(f"cell {cell.name}: preset {cell.preset}, {cell.steps} steps, cfg "
        f"{cell.cfg}, {cell.image_hw}, {cell.chips} chip(s), seed "
        f"{args.seed}, {seconds:g} s, trace {args.trace}")

    faults: list[str] = []
    try:
        with serve(out_dir, env) as server:
            device = census(server)
            on_chip = device["platform"] == "tpu" \
                and device["count"] == cell.chips
            if not on_chip and not (args.rehearse
                                    and device["count"] == cell.chips):
                raise NoDevice(
                    f"the cell needs {cell.chips} TPU chip(s); JAX found "
                    f"{device['count']} x {device['platform']}")
            run = Run(cell, server, out_dir, args.seed, seconds,
                      bool(args.trace))
            run.warm_up()
            opened = server.request(METRICS)["metrics"]
            setup_s = time.monotonic() - T_START
            say(f"set-up done in {setup_s:.1f} s; the window opens")
            records = run.window()
            closed = server.request(METRICS)["metrics"]
            memory = server.request("/distributed/memory_stats")
            (out_dir / "metrics_close.json").write_text(json.dumps(closed))
            (out_dir / "memory_stats.json").write_text(json.dumps(memory))
            log_text = server.log_path.read_text(errors="replace")
            if "Traceback (most recent call last)" in log_text:
                faults.append("an exception in the server log:\n"
                              + server.log_tail(40))
    except NoDevice as e:
        print(f"[cdtbench] {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    except BenchFailure as e:
        print(f"[cdtbench] FAILED: {e}", file=sys.stderr)
        return EXIT_FAILED

    faults += check_golden(cell, out_dir)
    faults += check_images(cell, out_dir, records)
    done = [r for r in records if r["status"] == "success"]
    failed = len(records) - len(done)
    ctx = {
        "cell": cell, "opened": opened, "closed": closed, "memory": memory,
        "records": records, "requests": len(done),
        "steps": len(done) * cell.steps,
        "images": len(done) * cell.images_per_request,
        "span_s": stats.window_span(records) if records else 0.0,
        "setup_s": setup_s, "device": device, "trace": None,
        "step_flops": cell.step_flops,
    }
    say(f"window: {len(records)} requests, {failed} failed, "
        f"{ctx['images']} images in {ctx['span_s']:.2f} s; request seconds "
        + ", ".join(f"{r['seconds']:.3f}" for r in records[:100]))

    # nothing compiles inside the window, and nothing is answered from
    # the content cache but a repeated (negative) prompt's conditioning
    for name, field, match, what in (
            ("cdt_xla_compile_seconds", "count", None, "executables compiled"),
            ("cdt_pipeline_compile_seconds", "count", None,
             "programs called for the first time"),
            ("cdt_cache_hits_total", "value", {"tier": "^(?!conditioning$)"},
             "answers from the content cache")):
        moved = (readers.total(closed, name, match, field, cell)
                 - readers.total(opened, name, match, field, cell))
        if moved:
            faults.append(f"{moved:g} {what} inside the window")

    section = "per_layer" if args.trace else "end_to_end"
    wanted = cell.metrics(section)
    metrics: dict = {}
    line: dict = {}
    try:
        if args.trace:
            traced = run.tracing or {}
            window_s = (traced["stop_wall"][0] - traced["start_wall"][1]
                        if "stop_wall" in traced else None)
            # the counters as they stood around the TRACED request(s): what
            # a reader divides the traced programs' device time into
            ctx["traced"] = {k: traced[k] for k in
                             ("opened", "closed", "requests")} \
                if traced.get("requests") else None
            post = reduce_trace(cell, out_dir, window_s)
            for note in post["notes"]:
                say(f"post: {note}")
            ctx["trace"] = post["trace"]
            say(f"traced {traced.get('requests')} request(s); xplane "
                f"{post.get('xplane_bytes', 0) / 2**20:.1f} MiB; step "
                f"operations {ctx['step_flops']}")
        for m in wanted:
            value = (readers.read(m["name"], ctx) if args.trace
                     else END_TO_END[m["name"]](ctx)) if done else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    except BenchFailure as e:
        print(f"[cdtbench] FAILED: {e}", file=sys.stderr)
        return EXIT_FAILED
    finally:
        for name in ("output", "profile", "content_cache"):
            shutil.rmtree(out_dir / name, ignore_errors=True)

    peaks = [(d.get("stats") or {}).get("peak_bytes_in_use") or 0
             for d in memory["devices"]]
    say("peak bytes in use per device: "
        + ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks))
    device["memory_peak_bytes"] = max(peaks) if peaks else 0
    if args.trace and ctx["trace"] is not None:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
    for fault in faults:
        say(f"FAULT: {fault}")
    correct = on_chip and not faults and failed == 0 and bool(done)
    if not on_chip:
        # a rehearsal's numbers are CPU numbers: shown above this line for
        # the eye, never under a metric's name
        say(f"rehearsal values (not device metrics): "
            f"{json.dumps({k: v['value'] for k, v in metrics.items()})}")
        metrics = {}
        line.pop("breakdown", None)
    line = {"correct": correct, "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device, **line}
    print(json.dumps(line), flush=True)
    return 0 if correct else EXIT_INCORRECT


if __name__ == "__main__":
    sys.exit(main())
