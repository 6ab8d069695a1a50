"""A cell assembled from its data files, found by the names in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json`` and
the mix's ``workflows/<graph>.json``. Nothing here knows a cell by name."""

from __future__ import annotations

import copy
import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    graph: dict            # the prompt graph every request starts from
    bench: dict            # BENCHMARK.json
    rehearsal: bool = False

    @property
    def preset(self) -> str:
        return self.config["rehearsal_preset" if self.rehearsal else "preset"]

    @property
    def sampler_inputs(self) -> dict:
        node = self.graph[self.traffic["nodes"]["sampler"]]
        return {k: literal(self.graph, v) for k, v in node["inputs"].items()
                if not _is_link(v) or _resolvable(self.graph, v)}

    @property
    def cfg(self) -> float:
        return float(self.sampler_inputs.get("cfg", 1.0))

    @property
    def steps(self) -> int:
        return int(self.sampler_inputs["steps"])

    @property
    def image_hw(self) -> tuple[int, int]:
        s = self.sampler_inputs
        return int(s["height"]), int(s["width"])

    @property
    def images_per_request(self) -> int:
        n = self.traffic.get("images_per_request", "chips")
        per_device = int(self.sampler_inputs.get("batch_per_device", 1))
        return (self.chips if n == "chips" else int(n)) * per_device

    @property
    def step_batch(self) -> int:
        """The batch one denoise step runs on a chip: doubled under CFG."""
        per_device = int(self.sampler_inputs.get("batch_per_device", 1))
        return per_device * (2 if self.cfg != 1.0 else 1)

    @property
    def step_key(self) -> str:
        height, width = self.image_hw
        return f"{height}x{width}.b{self.step_batch}"

    @property
    def step_flops(self) -> float | None:
        """One step's operations on one chip, pinned in the configuration's
        file under the cell's size; None where it has no such entry."""
        return self.config.get("step_flops", {}).get(self.step_key)

    def metrics(self, section: str) -> list[dict]:
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those
        with no ``workloads`` key, and those that list this cell."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def request_graph(self, seed: int, prompt: str, prefix: str) -> dict:
        graph = copy.deepcopy(self.graph)
        nodes = self.traffic["nodes"]
        for key, value in (("seed", seed), ("prompt", prompt),
                           ("save_prefix", prefix)):
            node_id, field = nodes[key]
            graph[node_id]["inputs"][field] = value
        return graph


def _is_link(value) -> bool:
    return isinstance(value, list) and len(value) == 2 \
        and isinstance(value[0], str)


_LITERAL_FIELD = {"DistributedValue": "default_value",
                  "DistributedSeed": "seed"}


def _resolvable(graph: dict, value) -> bool:
    return graph.get(value[0], {}).get("class_type") in _LITERAL_FIELD


def literal(graph: dict, value):
    """An input's value, following a link into one of the value nodes."""
    if not _is_link(value):
        return value
    node = graph[value[0]]
    return node["inputs"][_LITERAL_FIELD[node["class_type"]]]


def assemble(name: str, rehearsal: bool = False, root: Path = ROOT,
             here: Path = HERE) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(it has {sorted(entries)})")
    entry = entries[name]
    config = load_json(here / "configs" / f"{entry['config']}.json")
    traffic = load_json(here / "traffic" / f"{entry['traffic']}.json")
    graph = load_json(here / "workflows" / f"{traffic['graph']}.json")
    graph.pop("_meta", None)
    overrides = dict(traffic.get("overrides", {}))
    if rehearsal:
        for node_id, inputs in traffic.get("rehearsal_overrides",
                                           {}).items():
            overrides[node_id] = {**overrides.get(node_id, {}), **inputs}
    for node_id, inputs in overrides.items():
        graph[node_id]["inputs"].update(inputs)
    cell = Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, graph=graph, bench=bench,
                rehearsal=rehearsal)
    node_id, field = traffic["nodes"]["checkpoint"]
    graph[node_id]["inputs"][field] = cell.preset
    return cell
