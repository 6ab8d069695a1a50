"""Per-layer metrics, each read by the small reader its own data file
names: ``layer_metrics/<name>.json`` (reader, series, match, unit) and,
where the arithmetic is not expressible as data, ``<name>.py`` beside it
with ``read(ctx) -> float | None``. A reader that finds nothing to read
returns None and the metric is left out of the line.

``ctx`` is what one run knows: ``cell``, the ``/distributed/metrics.json``
snapshots ``opened`` (when the window opens) and ``closed``, ``memory``
(``/distributed/memory_stats`` after the window), the per-request
``records``, ``requests``, ``steps`` and ``images`` completed in the
window, ``trace`` (the reduction, or None), ``step_flops`` and ``device``;
in a traced run also ``traced``: the two snapshots taken as the profiler
started and stopped (``opened``, ``closed``) and the ``requests`` between
them, or None where the profile never closed — the counts that belong to the
traced programs' device time.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

from cdtbench import trace_reduce
from cdtbench.server import series

HERE = Path(__file__).resolve().parent / "layer_metrics"


def _matches(labels: dict, match: dict, cell) -> bool:
    for key, pattern in (match or {}).items():
        if pattern.startswith("@"):      # a pattern the configuration owns
            pattern = cell.config.get("programs", {}).get(pattern[1:])
            if pattern is None:
                return False
        if not re.search(pattern, str(labels.get(key, ""))):
            return False
    return True


def total(snapshot: dict, name: str, match: dict | None, field: str,
          cell) -> float:
    """Sum of ``field`` (``sum``/``count`` of a histogram, ``value`` of a
    counter or gauge) over the series whose labels match."""
    return float(sum(s.get(field, 0.0) for s in series(snapshot, name)
                     if _matches(s.get("labels", {}), match, cell)))


def _over(ctx, spec, name, match, field) -> float:
    cell = ctx["cell"]
    if spec.get("over", "window") == "open":
        return total(ctx["opened"], name, match, field, cell)
    return (total(ctx["closed"], name, match, field, cell)
            - total(ctx["opened"], name, match, field, cell))


def _per(ctx, spec) -> float | None:
    per = spec.get("per")
    if per is None:
        return 1.0
    return float(ctx[per]) or None


def read_histogram(ctx, spec):
    value = _over(ctx, spec, spec["series"], spec.get("match"),
                  spec.get("field", "sum"))
    per = _per(ctx, spec)
    if per is None or (value == 0.0 and spec.get("zero_is_missing")):
        return None
    return value * spec.get("scale", 1.0) / per


def read_counter_share(ctx, spec):
    label = spec["label"]
    part = _over(ctx, spec, spec["series"], {label: f"^{spec['of']}$"},
                 "value")
    whole = sum(_over(ctx, spec, spec["series"], {label: f"^{v}$"}, "value")
                for v in spec["among"])
    return None if whole == 0 else 100.0 * part / whole


def read_memory_peak(ctx, spec):
    peaks = [(d.get("stats") or {}).get("peak_bytes_in_use")
             for d in ctx["memory"]["devices"]]
    peaks = [p for p in peaks if p]
    return max(peaks) * spec.get("scale", 1.0) if peaks else None


def read_trace_value(ctx, spec):
    return None if ctx["trace"] is None else ctx["trace"].get(spec["key"])


def read_trace_share(ctx, spec):
    if ctx["trace"] is None:
        return None
    return trace_reduce.share_pct(ctx["trace"], spec["match"])


def read_trace_phase(ctx, spec):
    """Mean seconds of one program of the phase, from the trace."""
    if ctx["trace"] is None:
        return None
    phase = ctx["trace"]["phase_seconds"].get(spec["phase"])
    if not phase or not phase["count"]:
        return None
    return phase["seconds"] / phase["count"] * spec.get("scale", 1.0)


def read_client_stat(ctx, spec):
    times = [r["seconds"] for r in ctx["records"]
             if r["status"] == "success"]
    if not times:
        return None
    return {"max": max, "min": min}[spec["stat"]](times) \
        * spec.get("scale", 1.0)


READERS = {"histogram": read_histogram, "counter_share": read_counter_share,
           "memory_peak": read_memory_peak, "trace_value": read_trace_value,
           "trace_share": read_trace_share, "trace_phase": read_trace_phase,
           "client_stat": read_client_stat}


def spec_of(name: str, here: Path = HERE) -> dict:
    return json.loads((here / f"{name}.json").read_text())


def read(name: str, ctx: dict, here: Path = HERE) -> float | None:
    spec = spec_of(name, here)
    if spec["reader"] == "python":
        module_spec = importlib.util.spec_from_file_location(
            f"cdtbench_layer_metric_{name}", here / f"{name}.py")
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        return module.read(ctx)
    return READERS[spec["reader"]](ctx, spec)
