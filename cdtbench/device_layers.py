"""The device's busy seconds, named by the program's own layers.

The program traces its work under ``cdt.<layer>`` scopes
(``comfyui_distributed_tpu/telemetry/device_scopes.py``, PR 34); the
compiler copies the scope into each instruction's ``op_name`` and the TPU
profiler writes it, with the compiler's operation counts, into the EVENT
METADATA of the device planes: ``tf_op``, ``hlo_category``, ``flops``,
``model_flops``, ``bytes_accessed``, ``source``. ``jax.profiler.ProfileData``
shows none of that, so this module reads the file itself: ``read_space`` is
a reader of the protobuf wire format for the six messages of
``tsl/profiler/protobuf/xplane.proto`` (``XSpace``, ``XPlane``, ``XLine``,
``XEvent``, ``XStat``, ``XEventMetadata`` / ``XStatMetadata``), in plain
Python: no JAX, no tensorflow.

``report`` joins every ``XLA Ops`` event to its metadata, takes SELF times
as ``trace_reduce.self_times`` does, and resolves each operation to the
innermost ``cdt.<layer>`` of its ``tf_op``; an operation with none is
``(unnamed)``. An operation the COMPILER put there (no ``tf_op`` at all: the
asynchronous copies and slices that bring a weight in ahead of its use, and
the waits for them) is counted with the operation that reads its result
(``adopt``); each row says how many of its seconds came that way. Control
flow (``while``, ``conditional``, ``call``) adds its
own seconds (what no child covers) where its ``tf_op`` resolves, and no
operations, FLOPs or bytes: its children carry those. So every busy second
lands in exactly one row and the rows sum to ``trace_reduce``'s ``busy_s``.
``DEVICE_LAYERS.md`` says what a fused operation's name means.

``run`` is the subprocess with a time limit of its own, once a traced run:
the answer is kept in ``<out_dir>/device_layers.json`` beside the key of
the trace it was read from, and every later reader of the same trace takes
it from there — also when the answer is "nothing to read" (a program
without scopes, the parent of PR 34) or "not read in time".

    python -m cdtbench.device_layers <profile dir> <answer.json> [phases json]
"""

from __future__ import annotations

import json
import re
import struct
import subprocess
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from cdtbench.server import ROOT  # noqa: E402
from cdtbench.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,  # noqa: E402
                                   family, find_xplane, phase_of, self_times,
                                   short_name)

LAYER = re.compile(r"cdt\.([A-Za-z0-9_]+)")
INSTRUCTION = re.compile(r"%?([\w.\-]+) = ")     # "%copy-done.2 = bf16[...] ..."
OPERAND = re.compile(r"%([\w.\-]+)")
UNNAMED = "(unnamed)"
CONTROL_FLOW = ("while", "conditional", "call")     # hlo_category
LOOP_NAMES = ("while", "body", "cond", "closed_call")   # a tf_op's last part
TIME_LIMIT_S = 120.0
ANSWER_NAME = "device_layers.json"
PS = 1e-12                                  # a trace's times are picoseconds
TOP_ROWS, TOP_KEPT, TOP_UNNAMED = 3, 12, 24      # printed, kept, kept

# --- the wire format -----------------------------------------------------------

VARINT, FIXED64, LEN, FIXED32 = 0, 1, 2, 5


def fields(buf, pos: int, end: int):
    """``(field number, wire type, value)`` of one message's fields; a
    length-delimited value is its ``(start, end)`` in ``buf``, a fixed one
    its raw bytes' offset (``(start, end)`` too)."""
    while pos < end:
        tag = shift = 0
        while True:
            byte = buf[pos]
            pos += 1
            tag |= (byte & 0x7F) << shift
            if byte < 0x80:
                break
            shift += 7
        number, kind = tag >> 3, tag & 7
        if kind == VARINT:
            value = shift = 0
            while True:
                byte = buf[pos]
                pos += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            yield number, kind, value
        elif kind == LEN:
            size = shift = 0
            while True:
                byte = buf[pos]
                pos += 1
                size |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            yield number, kind, (pos, pos + size)
            pos += size
        elif kind in (FIXED64, FIXED32):
            size = 8 if kind == FIXED64 else 4
            yield number, kind, (pos, pos + size)
            pos += size
        else:
            raise ValueError(f"wire type {kind} at byte {pos}: not a "
                             "message this reader knows")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def read_stat(buf, span, stat_names: dict):
    """One ``XStat`` → ``(name, value)``; a ``ref_value`` is the name of the
    stat metadata it points at (how the profiler stores repeated strings)."""
    name = value = None
    for number, kind, v in fields(buf, *span):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack_from("<d", buf, v[0])[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(buf, v)
        elif number == 6:
            value = bytes(buf[v[0]:v[1]])
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf, span):
    key = value = None
    for number, _, v in fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def read_events(buf, span) -> list[tuple[int, int, int]]:
    """One ``XLine``'s events as ``(metadata id, offset ps, duration
    ps)``; an event's own stats are not read."""
    events = []
    for number, _, v in fields(buf, *span):
        if number != 4:
            continue
        meta = offset = duration = 0
        for n, kind, x in fields(buf, *v):
            if kind != VARINT:
                continue
            if n == 1:
                meta = x
            elif n == 2:
                offset = x
            elif n == 3:
                duration = x
        events.append((meta, offset, duration))
    return events


def read_plane(buf, span, wanted_lines=(OPS_LINE, MODULES_LINE)) -> dict | None:
    """One ``XPlane`` if it is a device's: its name, its own stats (the
    peaks), the wanted lines' events and every event metadata with its
    stats. None for any other plane, whose lines are never walked."""
    name, lines, event_meta, stat_meta, stats = "", [], [], [], []
    for number, kind, v in fields(buf, *span):
        if kind != LEN:
            continue
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_meta.append(v)
        elif number == 5:
            stat_meta.append(v)
        elif number == 6:
            stats.append(v)
    if not DEVICE_PLANE.match(name):
        return None
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for number, _, v in fields(buf, *value):
            if number == 2:
                stat_names[key] = _text(buf, v)
    metadata = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        meta = {"name": "", "display_name": "", "stats": {}}
        for number, kind, v in fields(buf, *value):
            if number == 2:
                meta["name"] = _text(buf, v)
            elif number == 4:
                meta["display_name"] = _text(buf, v)
            elif number == 5:
                stat, stat_value = read_stat(buf, v, stat_names)
                meta["stats"][stat] = stat_value
        metadata[key] = meta
    plane = {"name": name, "metadata": metadata, "lines": {},
             "stats": dict(read_stat(buf, s, stat_names) for s in stats)}
    for span_ in lines:
        line_name, timestamp_ns = "", 0
        for number, kind, v in fields(buf, *span_):
            if number == 2:
                line_name = _text(buf, v)
            elif number == 3 and kind == VARINT:
                timestamp_ns = _signed(v)
            elif number == 4:
                break                      # events follow the line's header
        if line_name in wanted_lines:
            base = timestamp_ns * 1000
            plane["lines"][line_name] = [
                (meta, base + offset, duration)
                for meta, offset, duration in read_events(buf, span_)]
    return plane


def read_space(path: Path) -> list[dict]:
    """The device planes of an ``.xplane.pb``, in the order of their
    names."""
    buf = memoryview(Path(path).read_bytes())
    planes = []
    for number, kind, v in fields(buf, 0, len(buf)):
        if number == 1 and kind == LEN:
            plane = read_plane(buf, v)
            if plane is not None:
                planes.append(plane)
    return sorted(planes, key=lambda p: p["name"])


# --- from events to layers -----------------------------------------------------


def layer_of(tf_op: str | None) -> str:
    """The innermost ``cdt.<layer>`` of an operation's name stack."""
    found = LAYER.findall(tf_op or "")
    return found[-1] if found else UNNAMED


def describe(meta: dict) -> dict:
    """What one operation's metadata says, under this module's names."""
    stats = meta["stats"]
    name = short_name(meta["name"])
    category = stats.get("hlo_category") or ""
    flops = stats.get("model_flops") or stats.get("flops") or 0
    tf_op = (stats.get("tf_op") or "").rstrip(":")
    # the metadata's name is the whole instruction: its own name, then the
    # names of what it reads
    head = INSTRUCTION.match(meta["name"])
    return {"name": name, "family": family(name), "category": category,
            "control_flow": category in CONTROL_FLOW,
            "tf_op": tf_op, "layer": layer_of(tf_op), "adopted": False,
            "instruction": head.group(1) if head else name,
            "reads": (OPERAND.findall(meta["name"], head.end())
                      if head else []),
            "source": stats.get("source") or "",
            "flops": int(flops), "bytes": int(stats.get("bytes_accessed")
                                              or 0),
            "program_id": stats.get("program_id")}


def compilers_own(op: dict) -> bool:
    """Did the COMPILER put this operation there? It has no ``tf_op`` at
    all, or one that names a loop or a call and no primitive of the
    program's (``jit(seg_body)/while``: the halves of an asynchronous slice
    the scheduler hoisted, named after the loop they were made for)."""
    return not op["control_flow"] and (
        not op["tf_op"] or op["tf_op"].rsplit("/", 1)[-1] in LOOP_NAMES)


def adopt(ops: dict) -> None:
    """Give every operation the compiler inserted (``compilers_own``: an
    asynchronous copy or slice that brings a weight in ahead of its use, the
    wait for it, a layout copy) the layer of the operation that reads its
    result. No scope of the program can reach such an operation, and its
    seconds are the reader's wait for its operand. Followed through other
    such operations (``copy-start`` → ``copy-done`` → the fusion); where the
    readers lie in several layers, the one most of them lie in. An
    operation the program DID trace and left outside every scope stays
    ``(unnamed)``, and so does one nobody named reads."""
    programs: dict = {}
    for op in ops.values():
        programs.setdefault(op["program_id"], {})[op["instruction"]] = op
    for program in programs.values():
        readers: dict = {}
        for op in program.values():
            for name in op["reads"]:
                if name in program:
                    readers.setdefault(name, []).append(op)
        for op in program.values():
            if op["layer"] != UNNAMED or not compilers_own(op):
                continue
            votes: dict = {}
            seen, walk = {op["instruction"]}, [op]
            while walk:
                for reader in readers.get(walk.pop()["instruction"], ()):
                    if reader["instruction"] in seen:
                        continue
                    seen.add(reader["instruction"])
                    if reader["layer"] != UNNAMED and not reader["adopted"]:
                        votes[reader["layer"]] = votes.get(
                            reader["layer"], 0) + 1
                    elif compilers_own(reader):
                        walk.append(reader)
            if votes:
                op["layer"] = min(votes, key=lambda k: (-votes[k], k))
                op["adopted"] = True


def _row() -> dict:
    return {"seconds": 0.0, "adopted_seconds": 0.0, "ops": 0, "flops": 0,
            "bytes": 0, "counted_flops": 0, "counted_seconds": 0.0}


def _add(row: dict, op: dict, own_s: float, times: int) -> None:
    """``times`` executions of ``op`` and the seconds they took in all."""
    row["seconds"] += own_s
    if op["adopted"]:
        row["adopted_seconds"] += own_s
    if op["control_flow"]:
        return
    row["ops"] += times
    row["flops"] += times * op["flops"]
    row["bytes"] += times * op["bytes"]
    if op["flops"] > 0:
        row["counted_flops"] += times * op["flops"]
        row["counted_seconds"] += own_s


def chip_report(plane: dict, phases: dict) -> dict:
    """One chip: seconds, operations, FLOPs and bytes per layer and per
    (phase, layer), seconds per ``hlo_category`` in each layer, the
    costliest rows of each layer and the unnamed seconds by name."""
    ops = {key: describe(meta) for key, meta in plane["metadata"].items()}
    adopt(ops)
    programs = {}
    for key, _, _ in plane["lines"].get(MODULES_LINE, ()):
        module = plane["metadata"][key]["name"]
        found = re.search(r"\((\d+)\)$", module)
        if found:
            programs[int(found.group(1))] = phase_of(module, phases)
    events = plane["lines"].get(OPS_LINE, ())
    layers: dict = {}
    by_phase: dict = {}
    categories: dict = {}
    rows: dict = {}
    # whole picoseconds in, so that back-to-back operations never look
    # nested by a rounding; self_times scales what it is given by 1e-9.
    # Summed per instruction first: some hundred thousand events are a few
    # thousand instructions, and the rows below are made once for each
    own: dict = {}
    for key, seconds in self_times(events):
        total = own.setdefault(key, [0.0, 0])
        total[0] += seconds
        total[1] += 1
    for key, (seconds, times) in own.items():
        own_s = seconds * (PS / 1e-9)
        op = ops[key]
        layer = op["layer"]
        flops = 0 if op["control_flow"] else times * op["flops"]
        _add(layers.setdefault(layer, _row()), op, own_s, times)
        phase = programs.get(op["program_id"], "other")
        _add(by_phase.setdefault(phase, {}).setdefault(layer, _row()), op,
             own_s, times)
        category = categories.setdefault(layer, {}).setdefault(
            op["category"] or "(none)", {"seconds": 0.0, "flops": 0})
        category["seconds"] += own_s
        category["flops"] += flops
        if layer == UNNAMED:
            at = (_prefix(op["tf_op"]), op["family"])
        elif op["adopted"]:
            at = (f"(the compiler's {op['family']}, for what reads it)", "")
        else:
            at = (op["tf_op"], op["source"])
        row = rows.setdefault(layer, {}).setdefault(at, [0.0, 0, 0])
        row[0] += own_s
        row[1] += times
        row[2] += flops
    busy_s = sum(row["seconds"] for row in layers.values())
    return {"plane": plane["name"], "busy_s": busy_s, "layers": layers,
            "phases": by_phase, "categories": categories, "rows": rows,
            "events": len(events)}


def _prefix(tf_op: str, parts: int = 6) -> str:
    """An unnamed operation's place: its name stack without the module
    path's tail, short enough to group by and long enough to grep for."""
    return "/".join(tf_op.split("/")[:parts]) or "(no tf_op)"


def _mean_rows(per_chip: list[dict]) -> dict:
    """Field by field, the mean over the chips (a layer one chip lacks
    counts as zero there)."""
    n = len(per_chip)
    out: dict = {}
    for rows in per_chip:
        for layer, row in rows.items():
            mean = out.setdefault(layer, {k: 0 for k in row})
            for k, v in row.items():
                mean[k] += v / n
    return out


def _rates(row: dict) -> dict:
    """A row with what it achieved: TFLOP/s and GB/s over its seconds."""
    seconds = row["seconds"]
    return {**row,
            "tflops_per_s": row["flops"] / seconds / 1e12 if seconds else None,
            "gb_per_s": row["bytes"] / seconds / 1e9 if seconds else None}


def report(planes: list[dict], phases: dict | None = None) -> dict | None:
    """The layers of a traced window: per chip, and as the mean over the
    chips with each layer's spread between them (seconds by category and
    the costliest rows are the first chip's). None where no operation of
    any chip carries a ``cdt.<layer>`` scope: the program has none (the
    parent of PR 34), and nothing is made up."""
    chips = [chip_report(p, phases or {}) for p in planes
             if p["lines"].get(OPS_LINE)]
    if not chips or all(set(c["layers"]) <= {UNNAMED} for c in chips):
        return None
    n = len(chips)
    layers = {k: _rates(v) for k, v in _mean_rows(
        [c["layers"] for c in chips]).items()}
    busy_s = sum(c["busy_s"] for c in chips) / n
    named_s = sum(v["seconds"] for k, v in layers.items() if k != UNNAMED)
    adopted_s = sum(v["adopted_seconds"] for v in layers.values())
    phase_names = sorted({p for c in chips for p in c["phases"]})
    by_phase = {p: {k: _rates(v) for k, v in _mean_rows(
        [c["phases"].get(p, {}) for c in chips]).items()}
        for p in phase_names}
    spread = {}
    for layer, row in layers.items():
        seen = [c["layers"].get(layer, {"seconds": 0.0})["seconds"]
                for c in chips]
        spread[layer] = (100.0 * (max(seen) - min(seen)) / row["seconds"]
                         if row["seconds"] else 0.0)
    first = chips[0]
    top = {}
    for layer, rows in first["rows"].items():
        ranked = sorted(rows.items(), key=lambda kv: -kv[1][0])
        keep = TOP_UNNAMED if layer == UNNAMED else TOP_KEPT
        top[layer] = [{"at": list(at), "seconds": s, "events": count,
                       "flops": flops}
                      for at, (s, count, flops) in ranked[:keep]]
    peaks = {k: v for k, v in planes[0]["stats"].items()
             if k and k.startswith("peak_") and v}
    return {
        "chips": n, "busy_s": busy_s, "named_s": named_s,
        "named_pct": 100.0 * named_s / busy_s if busy_s else None,
        "adopted_s": adopted_s,
        "layers": layers, "phases": by_phase, "spread_pct": spread,
        "categories": first["categories"], "top": top, "peaks": peaks,
        "per_chip": [{"plane": c["plane"], "busy_s": c["busy_s"],
                      "events": c["events"],
                      "seconds": {k: v["seconds"]
                                  for k, v in c["layers"].items()}}
                     for c in chips]}


def lines(answer: dict) -> list[str]:
    """The table a person reads: each layer's seconds, share, operations,
    achieved rates and costliest rows; then the unnamed seconds by name."""
    busy = answer["busy_s"]
    out = [f"device layers: {answer['chips']} chip(s), busy "
           f"{busy:.4f} s, {answer['named_s']:.4f} s "
           f"({answer['named_pct']:.2f}%) under a cdt.<layer> scope, "
           f"{answer['adopted_s']:.4f} s of them the compiler's own "
           "operations, by what reads them"]
    for layer, row in sorted(answer["layers"].items(),
                             key=lambda kv: -kv[1]["seconds"]):
        rate = (f"{row['tflops_per_s']:8.2f} TFLOP/s {row['gb_per_s']:8.1f} "
                "GB/s" if row["seconds"] else "")
        out.append(
            f"  {row['seconds']:9.6f} s {100 * row['seconds'] / busy:6.2f}%  "
            f"{layer:16s} x{row['ops']:<9.0f} {rate}  spread "
            f"{answer['spread_pct'][layer]:.2f}%  adopted "
            f"{row['adopted_seconds']:.6f} s")
        for at in answer["top"].get(layer, [])[:TOP_ROWS]:
            rate = at["flops"] / at["seconds"] / 1e12 if at["seconds"] else 0
            out.append(f"      {at['seconds']:9.6f} s x{at['events']:<7d} "
                       f"{rate:7.2f} TFLOP/s  {at['at'][0][-110:]}  "
                       f"{at['at'][1]}")
    for at in answer["top"].get(UNNAMED, [])[TOP_ROWS:]:
        out.append(f"  unnamed {at['seconds']:9.6f} s x{at['events']:<7d} "
                   f"{at['at'][1]:28s} {at['at'][0]}")
    for phase, rows in answer["phases"].items():
        total = sum(r["seconds"] for r in rows.values())
        out.append(f"  phase {phase}: {total:.6f} s: " + ", ".join(
            f"{k} {r['seconds']:.6f}" for k, r in sorted(
                rows.items(), key=lambda kv: -kv[1]["seconds"])))
    return out


# --- what the metric files read ------------------------------------------------


def _rows(answer: dict, phases) -> dict:
    if phases is None:
        return answer["layers"]
    out: dict = {}
    for phase in phases:
        for layer, row in answer["phases"].get(phase, {}).items():
            total = out.setdefault(layer, {"seconds": 0.0})
            total["seconds"] += row["seconds"]
    return out


def share_pct(answer: dict | None, layers, phases=None) -> float | None:
    """The named ``layers``' share of the device's busy seconds (of the
    given program ``phases``' seconds where given). None where none of
    them matched an operation: a scope the trace does not show is never
    read as 0%."""
    if answer is None:
        return None
    rows = _rows(answer, phases)
    whole = sum(row["seconds"] for row in rows.values())
    hit = [rows[layer]["seconds"] for layer in layers if layer in rows]
    if not hit or not whole:
        return None
    return 100.0 * sum(hit) / whole


def xla_mxu_pct(answer: dict | None, layer: str, peak_flops: float
                ) -> float | None:
    """The compiler's count of the layer's XLA operations that have one
    (``model_flops`` > 0) over THEIR self seconds, as a share of the
    compute peak. An operation without a count (a layout copy, a pad, a
    Pallas call that states no cost) is in neither sum. None where the
    layer has no counted operation."""
    row = (answer or {}).get("layers", {}).get(layer)
    if not row or not row["counted_seconds"] or not row["counted_flops"]:
        return None
    return 100.0 * row["counted_flops"] / row["counted_seconds"] / peak_flops


# --- the subprocess, once a traced run -----------------------------------------


def _key(xplane: Path) -> dict:
    stat = xplane.stat()
    return {"xplane": str(xplane), "bytes": stat.st_size,
            "mtime_ns": stat.st_mtime_ns}


def run(profile_dir: Path, answer_path: Path, phases: dict | None = None,
        say=print) -> dict | None:
    """``report`` of the profile under ``profile_dir``, read in a
    subprocess with a time limit, ONCE a trace: a second call for the same
    trace answers from ``answer_path``. None (and a line saying why) where
    there is nothing to read or the reader did not end in time."""
    xplane = find_xplane(Path(profile_dir))
    if xplane is None:
        return None
    key = _key(xplane)
    try:
        kept = json.loads(Path(answer_path).read_text())
        if kept.get("key") == key:
            return kept["report"]
    except (OSError, ValueError):
        pass
    why = None
    t0 = time.monotonic()
    try:
        done = subprocess.run(
            [sys.executable, "-m", "cdtbench.device_layers",
             str(profile_dir), str(answer_path), json.dumps(phases or {})],
            cwd=ROOT, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True, timeout=TIME_LIMIT_S)
        if done.returncode != 0:
            why = f"the reader failed: {done.stderr[-500:]}"
    except subprocess.TimeoutExpired:
        why = f"not read inside {TIME_LIMIT_S:g} s"
    if why is not None:
        say(f"device layers: {why}")
        Path(answer_path).write_text(json.dumps(
            {"key": key, "report": None, "why": why}))
        return None
    answer = json.loads(Path(answer_path).read_text())["report"]
    if answer is None:
        say("device layers: no operation of the trace carries a "
            "cdt.<layer> scope: nothing is read from it")
        return None
    say(f"device layers: read in {time.monotonic() - t0:.1f} s")
    for line in lines(answer):
        say(line)
    return answer


def of_run(ctx: dict) -> dict | None:
    """The traced run's report for a ``layer_metrics`` reader: None
    without a trace."""
    if ctx.get("trace") is None:
        return None
    from cdtbench.server import say

    cell = ctx["cell"]
    out_dir = ROOT / "chiprun_out" / "cdtbench" / cell.name
    return run(out_dir / "profile", out_dir / ANSWER_NAME,
               cell.config.get("trace_phases", {}), say)


def read_named(ctx: dict) -> float | None:
    answer = of_run(ctx)
    return None if answer is None else answer["named_pct"]


def read_share(ctx: dict, name: str) -> float | None:
    """``share_pct`` of the ``layers`` (and ``phases``) the metric's data
    file names."""
    from cdtbench.readers import spec_of

    spec = spec_of(name)
    return share_pct(of_run(ctx), spec["layers"], spec.get("phases"))


def read_xla_mxu(ctx: dict, name: str) -> float | None:
    """``xla_mxu_pct`` of the ``layer`` the metric's data file names,
    against the peak of the device the run was made on."""
    from cdtbench.flops import peak_flops
    from cdtbench.readers import spec_of

    answer = of_run(ctx)
    if answer is None:
        return None
    return xla_mxu_pct(answer, spec_of(name)["layer"],
                       peak_flops(ctx["device"]["kind"]))


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:])
    profile_dir, answer_path = argv[:2]
    phases = json.loads(argv[2]) if len(argv) > 2 else {}
    xplane = find_xplane(Path(profile_dir))
    answer = None
    t0 = time.monotonic()
    if xplane is not None:
        answer = report(read_space(xplane), phases)
        if answer is not None:
            answer["read_s"] = time.monotonic() - t0
    Path(answer_path).write_text(json.dumps(
        {"key": None if xplane is None else _key(xplane),
         "report": answer}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
