"""The device's idle seconds, named by what the host was doing.

While a profile session runs, the program mirrors its synchronous spans
into the trace as annotations named ``cdt.<span name>`` (PR 24,
``telemetry/spans.py``), so a traced request's ``.xplane.pb`` holds the
host's spans and the chip's operations on one clock. ``attribute`` lays
the one over the other: every idle instant of the first chip, between the
first and the last mirrored span, goes to the span that was opened last
among those open at that instant — the innermost on its own thread, and
the newest across threads (a progress callback on a runtime thread beside
the ``program.wait`` of the thread that launched).

``load`` needs JAX (``jax.profiler.ProfileData``) and runs like
``post.py``, in a subprocess held to the CPU after the serve child has
exited: ``python -m cdtbench.host_spans <profile dir> <answer.json>``.
``run`` is that subprocess with a time limit of its own; it answers None,
and says why, rather than fail the benchmark's run. A program without the
annotations (the parent of PR 24) leaves no ``cdt.`` event, and the answer
is None.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
from pathlib import Path

from cdtbench.server import ROOT
from cdtbench.stats import merged_intervals

PREFIX = "cdt."
NO_SPAN = "(no span)"
UNNAMED = ("node.",)          # a node span says where, not what
LARGE_GAP_S = 0.010
NS = 1e-9
TIME_LIMIT_S = 240.0


def load(xplane: Path) -> tuple[list, list, dict]:
    """``(busy, spans, threads)``: the first device plane's operations as
    ``(start_ns, end_ns)``, the host's ``cdt.*`` events as ``(name,
    start_ns, end_ns)`` with the prefix taken off, and how many of each
    name every host line (a thread) holds."""
    import jax

    from cdtbench.trace_reduce import DEVICE_PLANE, OPS_LINE

    data = jax.profiler.ProfileData.from_file(str(xplane))
    planes = sorted(data.planes, key=lambda p: p.name)
    busy: list = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    busy = [(float(ev.start_ns),
                             float(ev.start_ns) + float(ev.duration_ns))
                            for ev in line.events]
            break
    spans, threads = [], {}
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    name, start = ev.name[len(PREFIX):], float(ev.start_ns)
                    spans.append((name, start,
                                  start + float(ev.duration_ns)))
                    counts = threads.setdefault(
                        f"{plane.name} {line.name}", {})
                    counts[name] = counts.get(name, 0) + 1
    return busy, spans, threads


def timeline(spans) -> list[tuple[float, float, str]]:
    """The window cut at every span's start and end: ``(t0, t1, name)``
    in order, each piece named by the span opened last among those that
    cover it (the shorter on a tie), ``NO_SPAN`` where none does."""
    points = sorted({t for _, start, end in spans for t in (start, end)})
    pieces: list[list] = []
    for t0, t1 in zip(points, points[1:]):
        covering = [(start, start - end, name) for name, start, end in spans
                    if start <= t0 and end >= t1]
        name = max(covering)[2] if covering else NO_SPAN
        if pieces and pieces[-1][2] == name:
            pieces[-1][1] = t1
        else:
            pieces.append([t0, t1, name])
    return [(t0, t1, name) for t0, t1, name in pieces]


def attribute(busy, spans) -> dict | None:
    """Idle seconds of the chip per span name, inside the window from the
    first span's start to the last span's end. None without a span or
    without an operation: nothing is made up."""
    if not spans or not busy:
        return None
    merged = merged_intervals(busy)
    starts = [s for s, _ in merged]
    before = [0.0]                       # busy ns in intervals before the i-th
    for s, e in merged:
        before.append(before[-1] + e - s)

    def busy_until(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = merged[i - 1]
        return before[i - 1] + min(t, e) - s

    pieces = timeline(spans)
    window = (pieces[0][0], pieces[-1][1])
    by_span: dict = {}
    for t0, t1, name in pieces:
        idle = (t1 - t0) - (busy_until(t1) - busy_until(t0))
        by_span[name] = by_span.get(name, 0.0) + idle * NS
    # the long gaps one by one: where a person looks first
    gaps, cursor = [], window[0]
    for s, e in merged:
        if e <= window[0]:
            continue
        if s >= window[1]:
            break
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    large = []
    for g0, g1 in gaps:
        if (g1 - g0) * NS < LARGE_GAP_S:
            continue
        split: dict = {}
        for t0, t1, name in pieces:
            overlap = min(g1, t1) - max(g0, t0)
            if overlap > 0:
                split[name] = split.get(name, 0.0) + overlap * NS
        large.append({"at_s": (g0 - window[0]) * NS,
                      "seconds": (g1 - g0) * NS, "by_span": split})
    idle_s = sum(by_span.values())
    named_s = sum(s for name, s in by_span.items()
                  if name != NO_SPAN and not name.startswith(UNNAMED))
    return {"window_s": (window[1] - window[0]) * NS, "idle_s": idle_s,
            "named_s": named_s, "by_span": by_span, "large_gaps": large,
            "idle_named_pct": 100.0 * named_s / idle_s if idle_s else None}


def lines(answer: dict) -> list[str]:
    """The table a person reads: idle seconds per span name, then the
    longest gaps, in order of time, with their split."""
    out = [f"host spans: window {answer['window_s']:.4f} s, chip idle "
           f"{answer['idle_s']:.4f} s of it, {answer['named_s']:.4f} s "
           "under a span that says what the host did"]
    for name, seconds in sorted(answer["by_span"].items(),
                                key=lambda kv: -kv[1]):
        out.append(f"  idle {seconds:9.6f} s  {name}")
    longest = sorted(answer["large_gaps"], key=lambda g: -g["seconds"])[:12]
    for gap in sorted(longest, key=lambda g: g["at_s"]):
        split = ", ".join(f"{name} {seconds:.6f}" for name, seconds in
                          sorted(gap["by_span"].items(),
                                 key=lambda kv: -kv[1]))
        out.append(f"  gap at {gap['at_s']:.4f} s, {gap['seconds']:.6f} s: "
                   f"{split}")
    for thread, counts in answer.get("threads", {}).items():
        out.append(f"  thread {thread}: " + ", ".join(
            f"{name} x{n}" for name, n in sorted(counts.items())))
    return out


def run(profile_dir: Path, answer_path: Path, say=print) -> dict | None:
    """``attribute`` of the profile under ``profile_dir``, computed in a
    subprocess held to the CPU; None (and a line saying why) where there
    is nothing to read or the subprocess does not end in time."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    try:
        done = subprocess.run(
            [sys.executable, "-m", "cdtbench.host_spans", str(profile_dir),
             str(answer_path)],
            cwd=ROOT, env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            timeout=TIME_LIMIT_S)
    except subprocess.TimeoutExpired:
        say(f"host spans: not read inside {TIME_LIMIT_S:g} s")
        return None
    if done.returncode != 0:
        say(f"host spans: the reader failed: {done.stderr[-500:]}")
        return None
    answer = json.loads(Path(answer_path).read_text())
    if answer is None:
        say("host spans: the trace has no cdt.* annotation or no device "
            "operation: nothing is read from it")
        return None
    for line in lines(answer):
        say(line)
    return answer


def main(argv=None) -> int:
    from cdtbench.trace_reduce import find_xplane

    profile_dir, answer_path = (argv or sys.argv[1:])[:2]
    xplane = find_xplane(Path(profile_dir))
    answer = None
    # names are stored as plain bytes: a trace without the prefix anywhere
    # (the parent of PR 24, whose profile holds millions of Python-tracer
    # events) is answered without walking its events
    if xplane is not None and PREFIX.encode() in xplane.read_bytes():
        busy, spans, threads = load(xplane)
        answer = attribute(busy, spans)
        if answer is not None:
            answer["threads"] = threads
    Path(answer_path).write_text(json.dumps(answer))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
