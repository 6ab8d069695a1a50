"""Atomic JSON persistence shared by the merge-on-save registries.

Artifacts that persist next to the XLA compilation cache and are written
by multiple processes (serving master, warmup CLI, a second controller):
the shape catalog (``cluster/shape_catalog.py``) and the content cache's
index (``cluster/cache/store.py``). Both follow the same contract:

- **reads never crash**: a missing, unreadable, or garbled file degrades
  to "no data" (the caller logs at debug level and starts empty);
- **writes are atomic**: payload lands in a sibling ``.tmp`` file first
  and is ``os.replace``d into place, so a concurrent reader never sees a
  half-written file;
- **savers merge first**: callers re-read the file before writing so
  concurrent writers union rather than clobber (the merge policy itself
  — set union vs keyed overlay — stays with the caller).

Extracted from the shape catalog's PR 4 implementation so the tuning
table can't drift from it.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Optional

from .logging import debug_log


def read_json(path: "Path | str") -> Optional[Any]:
    """Parsed JSON content of ``path``, or None when the file is missing,
    unreadable, or not valid JSON (never raises)."""
    try:
        return json.loads(Path(path).read_text())
    except (OSError, ValueError, AttributeError):
        return None


def atomic_write_json(path: "Path | str", payload: Any,
                      indent: int = 1) -> bool:
    """Serialize ``payload`` and atomically replace ``path`` with it
    (tmp + rename; parent directories are created). Returns False —
    never raises — when the write fails."""
    path = Path(path)
    tmp = path.with_suffix(".tmp")
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp.write_text(json.dumps(payload, indent=indent))
        os.replace(tmp, path)
        return True
    except (OSError, TypeError, ValueError) as e:
        debug_log(f"jsonio: atomic write to {path} failed: {e}")
        return False
