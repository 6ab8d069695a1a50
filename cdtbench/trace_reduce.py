"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
per-operation time and labelled idle gaps.

``load`` needs JAX (``jax.profiler.ProfileData``) and is called only in the
post-processing subprocess, which runs with ``JAX_PLATFORMS=cpu`` after the
serve child has exited. ``reduce`` is plain arithmetic on what ``load``
returns, and is what the tests drive.

A trace here is ``{plane name: {line name: [(event name, start_ns,
duration_ns), ...]}}``. Device planes are ``/device:TPU:<n>``; on each the
line ``XLA Ops`` holds the operations as they ran on the chip and ``XLA
Modules`` the programs. Operations nest (a ``while`` spans its body), so
busy time is the UNION of their intervals and an operation's own time is
its duration less its children's.
"""

from __future__ import annotations

import re
from pathlib import Path

from cdtbench.stats import merged_intervals, union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_S = 50e-6
NS = 1e-9


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def short_name(event_name: str) -> str:
    """An operation's own name. The trace gives the whole HLO instruction
    (``%fusion.7 = bf16[...] fusion(%_flash_mha_fused.3, ...)``): matching
    on all of it would count every consumer of a kernel as the kernel."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def family(op_name: str) -> str:
    """``fusion.14677`` and ``fusion.3`` are one family, ``fusion``."""
    return re.sub(r"(\.\d+)+(\.clone)*$", "", op_name)


def load(path: Path, device_only: bool = True) -> dict:
    """Device planes' events (and, where asked, the host's: a traced
    request leaves millions of Python-tracer events nobody reads)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    trace: dict = {}
    for plane in data.planes:
        if device_only and not DEVICE_PLANE.match(plane.name):
            continue
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            if device_only and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines[line.name] = [
                (short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)) for ev in line.events]
    return trace


def inspect(trace: dict, top: int = 12) -> list[str]:
    """What a person looks at before trusting a regex: planes, lines,
    counts and the most frequent names."""
    out = []
    for plane, lines in trace.items():
        out.append(f"plane {plane}")
        for line, events in lines.items():
            out.append(f"  line {line!r}: {len(events)} events")
            if DEVICE_PLANE.match(plane):
                by_name: dict = {}
                for name, _, dur in events:
                    by_name[name] = by_name.get(name, 0.0) + dur
                for name, dur in sorted(by_name.items(),
                                        key=lambda kv: -kv[1])[:top]:
                    out.append(f"    {dur * NS:10.6f} s  {name[:160]}")
    return out


def self_times(events) -> list[tuple[str, float]]:
    """Each event's own seconds: its duration less that of the events
    nested inside it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own, stack = [], []          # stack of [name, end, own_ns]
    for name, start, dur in order:
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            own.append((done[0], done[2] * NS))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, end, dur])
    own.extend((s[0], s[2] * NS) for s in stack)
    return own


def phase_of(module_name: str, phases: dict) -> str:
    for phase, pattern in phases.items():
        if re.search(pattern, module_name):
            return phase
    return "other:" + re.sub(r"\(\d+\)$", "", module_name)[:40]


def label_gaps(ops, modules, phases: dict) -> list[tuple[str, float]]:
    """Every idle gap of one chip with a label made from the programs
    around it: ``inside <phase>`` where one program spans the gap, else
    ``<phase before> -> <phase after>``."""
    busy = merged_intervals((s, s + d) for _, s, d in ops)
    mods = sorted(modules, key=lambda e: e[1])
    gaps = []
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        seconds = (gap_end - gap_start) * NS
        if seconds < MIN_GAP_S:
            continue
        # programs run one after another and open a little before their
        # first operation: the last to start by the gap's start ran the
        # operation before it, the first to end after the gap's end runs
        # the operation after it
        before = after = None
        for m in mods:
            _, start, dur = m
            if start <= gap_start + 1:
                before = m
            if after is None and start + dur >= gap_end - 1:
                after = m
        if before is not None and before is after:
            label = f"inside {phase_of(before[0], phases)}"
        else:
            label = (f"{phase_of(before[0], phases) if before else 'start'}"
                     f" -> {phase_of(after[0], phases) if after else 'end'}")
        gaps.append((label, seconds))
    return gaps


def reduce(trace: dict, phases: dict | None = None, top: int = 10,
           window_s: float | None = None) -> dict | None:
    """The numbers the per-layer metrics read. None where the trace has
    no device plane (a CPU rehearsal): no device number is made up.
    ``window_s`` is the traced window as the client timed it (from the
    answer to ``profile/start`` to the call of ``profile/stop``); without
    it, the span of the events given."""
    phases = phases or {}
    planes = {n: l for n, l in trace.items()
              if DEVICE_PLANE.match(n) and l.get(OPS_LINE)}
    if not planes:
        return None
    if window_s is None:
        starts = [s for lines in trace.values() for ev in lines.values()
                  for _, s, _ in ev]
        ends = [s + d for lines in trace.values() for ev in lines.values()
                for _, s, d in ev]
        window_s = (max(ends) - min(starts)) * NS
    n = len(planes)
    busy_s = sum(
        union_seconds((s, s + d) for _, s, d in lines[OPS_LINE]) * NS
        for lines in planes.values()) / n
    op_seconds: dict = {}
    for lines in planes.values():
        for name, own in self_times(lines[OPS_LINE]):
            op_seconds[name] = op_seconds.get(name, 0.0) + own / n
    first = planes[sorted(planes)[0]]
    modules = first.get(MODULES_LINE, [])
    phase_seconds: dict = {}
    for name, _, dur in modules:
        phase = phase_of(name, phases)
        total, count = phase_seconds.get(phase, (0.0, 0))
        phase_seconds[phase] = (total + dur * NS, count + 1)
    gap_totals: dict = {}
    for label, seconds in label_gaps(first[OPS_LINE], modules, phases):
        total, count, longest = gap_totals.get(label, (0.0, 0, 0.0))
        gap_totals[label] = (total + seconds, count + 1,
                             max(longest, seconds))
    ranked_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    families: dict = {}
    for name, seconds in op_seconds.items():
        total, count = families.get(family(name), (0.0, 0))
        families[family(name)] = (total + seconds, count + 1)
    ranked_families = sorted(families.items(), key=lambda kv: -kv[1][0])
    ranked_gaps = sorted(gap_totals.items(), key=lambda kv: -kv[1][0])
    return {
        "chips": n, "window_s": window_s, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "op_seconds": dict(ranked_ops),
        "phase_seconds": {k: {"seconds": v[0], "count": v[1]}
                          for k, v in phase_seconds.items()},
        "device_ops": [[f"{name} (x{count} ops)", s]
                       for name, (s, count) in ranked_families[:top]],
        "idle_gaps": [[f"{label} (x{c}, longest {longest:.4f} s)", total]
                      for label, (total, c, longest) in ranked_gaps[:top]],
    }


def share_pct(reduced: dict, pattern: str) -> float | None:
    """Share of device busy time in operations whose name (or kernel
    metadata) matches ``pattern``; None where nothing matches, so that a
    name the trace does not show is never read as 0%."""
    hit = [s for name, s in reduced["op_seconds"].items()
           if re.search(pattern, name)]
    if not hit:
        return None
    return 100.0 * sum(hit) / reduced["busy_s"]
