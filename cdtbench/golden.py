"""The golden image: what the cell's served path answered for one fixed
request when the cell was recorded, held against every later run.

``goldens/<workload>.json`` names the request (a seed and a prompt of its
own, not drawn from ``--seed``: the weights are the registry's fixed random
ones, so the same request gives the same image in every run), the stride at
which the image is kept, and the tolerance; ``goldens/<workload>.png`` is
the recorded image, every ``stride``-th pixel of it. The cell's LAST warm-up
request is that request, so the check costs no request of its own and no
time in the window. A cell without both files is not correct.

This is a regression reference, recorded from the system under test, not an
independent one: it holds later PRs to the mathematics the cell had when it
was added (a dropped CFG branch, a skipped step, compute or weights in a
lower precision all move the image), and says nothing about whether that
mathematics was right. Only a ``benchmark`` PR may record a golden anew.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent / "goldens"
CANDIDATE = "golden_candidate.png"


def spec_of(workload: str, here: Path = HERE) -> dict | None:
    path = here / f"{workload}.json"
    return json.loads(path.read_text()) if path.is_file() else None


def distance(image, golden) -> dict:
    """How far two uint8 images of one shape lie apart, in 8-bit levels."""
    import numpy as np

    diff = np.abs(image.astype(np.int16) - golden.astype(np.int16))
    return {"mean_abs_levels": float(diff.mean()),
            "max_abs_levels": int(diff.max()),
            "share_over_8_levels_pct": float(100.0 * (diff > 8).mean())}


def check(workload: str, image, out_dir: Path, say, here: Path = HERE
          ) -> list[str]:
    """Hold the golden request's served ``image`` (full size, uint8) to
    the recorded one. Always leaves the candidate under ``out_dir``, which
    is how a golden is recorded: run the cell on the chip and copy
    ``golden_candidate.png`` to ``goldens/<workload>.png``. Answers the
    faults found."""
    import numpy as np
    from PIL import Image

    spec = spec_of(workload, here)
    if spec is None:
        return [f"no golden for {workload}: goldens/{workload}.json is "
                "missing (cdtbench/README.md, 'Add a cell')"]
    stride = int(spec.get("stride", 4))
    kept = np.ascontiguousarray(image[::stride, ::stride])
    Image.fromarray(kept).save(out_dir / CANDIDATE)
    path = here / f"{workload}.png"
    if not path.is_file():
        return [f"no golden image at goldens/{workload}.png: this run's is "
                f"left at {out_dir / CANDIDATE}"]
    golden = np.asarray(Image.open(path))
    if golden.shape != kept.shape:
        return [f"golden image {golden.shape} against served {kept.shape} "
                f"at stride {stride}"]
    d = distance(kept, golden)
    limit = float(spec["max_mean_abs_levels"])
    say(f"golden request against goldens/{workload}.png: mean |diff| "
        f"{d['mean_abs_levels']:.4f} of 255 levels (limit {limit:g}), max "
        f"{d['max_abs_levels']}, {d['share_over_8_levels_pct']:.3f}% of "
        "values over 8 levels")
    if d["mean_abs_levels"] > limit:
        return [f"the golden request's image lies {d['mean_abs_levels']:.3f} "
                f"levels (mean |diff|) from the recorded one; the limit is "
                f"{limit:g}: the served mathematics changed"]
    return []
