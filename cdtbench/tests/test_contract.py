"""``BENCHMARK.json`` against the contract the driver checks before any
run: keys, names, units, lengths, and that every name has its files."""

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_benchmark_json_meets_the_contract():
    raw = (ROOT / "BENCHMARK.json").read_text()
    assert len(raw.encode()) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["cdtbench"] and 1 <= b["run_seconds"] <= 51
    assert len(b["command"]) <= 32 and all(_line(w) for w in b["command"])
    configs = {c["name"]: c for c in b["configs"]}
    assert len(configs) == len(b["configs"]) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("cdtbench/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cells) == len(b["workloads"]) <= 24
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (ROOT / "cdtbench" / "traffic" / f"{w['traffic']}.json").is_file()
        for suffix in ("json", "png"):
            assert (ROOT / "cdtbench" / "goldens"
                    / f"{w['name']}.{suffix}").is_file()
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"]) and m["source"] in SOURCES
        assert (ROOT / "cdtbench" / "layer_metrics" / f"{m['name']}.json").is_file()
        # the metric it moves is reported in every cell where this one is
        moved = e2e[m["moves"]]
        where = set(m.get("workloads", cells))
        assert where <= set(moved.get("workloads", cells)), m["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for name in cells:      # setup_s, another end-to-end and a per-layer one
        mine = [m for m in b["end_to_end"]
                if name in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(name in m.get("workloads", cells) for m in b["per_layer"])
    for path in (ROOT / "cdtbench").rglob("*"):
        if "__pycache__" in path.parts or path.suffix == ".pyc":
            continue
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+",
                            str(path.relative_to(ROOT))), path
