"""What needs JAX after the serve child has exited: the trace reduction.
Run as a subprocess with
``JAX_PLATFORMS=cpu`` so that the benchmark's own process stays off the
chip: ``python -m cdtbench.post <request.json> <answer.json>``."""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    request_path, answer_path = (argv or sys.argv[1:])[:2]
    request = json.loads(Path(request_path).read_text())
    answer: dict = {"trace": None, "notes": []}
    if request.get("trace_dir"):
        from cdtbench import trace_reduce

        xplane = trace_reduce.find_xplane(Path(request["trace_dir"]))
        if xplane is None:
            answer["notes"].append("no .xplane.pb under the profile dir")
        else:
            trace = trace_reduce.load(xplane)
            Path(request["inspect_path"]).write_text(
                "\n".join(trace_reduce.inspect(trace)) + "\n")
            answer["trace"] = trace_reduce.reduce(
                trace, request.get("phases") or {},
                window_s=request.get("window_s"))
            answer["xplane_bytes"] = xplane.stat().st_size
            if answer["trace"] is None:
                answer["notes"].append(
                    "the trace has no /device:TPU plane: no device "
                    "number is read from it")
    Path(answer_path).write_text(json.dumps(answer))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
