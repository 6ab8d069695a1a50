"""The two readers of the set-up ledger's OWNERS (``abstract_pass_s``,
``cold_compile_s``: data files, not listed in ``BENCHMARK.json`` — its
``per_layer`` is full) against a recorded ``metrics.json`` snapshot
(``data/setup_owners.metrics.json``: a CPU rehearsal of
``sd3-medium.solo28`` through ``scripts/setup_timeline.py --rehearse``, cut
to the families read here, taken after the two warm-ups) and against the
older snapshot, of a program without the two families."""

import json
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import pytest

from cdtbench import readers

DATA = Path(__file__).resolve().parent / "data"
BUILD = "cdt_program_build_seconds"
UNDER = "cdt_program_build_under_seconds"
COLD = "cdt_program_cold_compile_seconds"


def _ctx(opened, setup_s=30.0):
    return {"cell": SimpleNamespace(config={}, name="recorded"),
            "opened": opened, "closed": opened, "setup_s": setup_s}


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "setup_owners.metrics.json").read_text())


@pytest.fixture(scope="module")
def parent():
    return json.loads((DATA / "setup_ledger.metrics.json").read_text())


@pytest.mark.parametrize("name, series, match", [
    ("abstract_pass_s", UNDER, {"under": "init_shapes"}),
    ("cold_compile_s", COLD, {}),
])
def test_a_data_file_reads_its_owner(recorded, name, series, match):
    spec = readers.spec_of(name)
    assert spec["reader"] == "histogram" and spec["over"] == "open"
    assert spec["series"] == series and spec["unit"] == "s"
    value = readers.read(name, _ctx(recorded))
    assert value == pytest.approx(sum(
        s["value"] for s in recorded[series]["series"]
        if all(s["labels"][k] == v for k, v in match.items())))
    assert value > 0


def test_the_abstract_pass_is_not_the_entries_own_trace(recorded):
    """``init_shapes``' own trace seconds are ``trace_s``'s, under whoever
    opened the pass; the reader holds what ran INSIDE it."""
    own = sum(s["sum"] for s in recorded[BUILD]["series"]
              if s["labels"] == {"program": "init_shapes", "phase": "trace"})
    inside = readers.read("abstract_pass_s", _ctx(recorded))
    assert own > 0 and inside != pytest.approx(own)
    assert inside + own < readers.read("trace_s", _ctx(recorded))


@pytest.mark.parametrize("phase", ["trace", "lower", "cache_key",
                                   "cache_read", "compile", "first_run"])
def test_by_owner_a_phase_adds_up_to_the_ledgers(recorded, phase):
    built = sum(s["sum"] for s in recorded[BUILD]["series"]
                if s["labels"]["phase"] == phase)
    under = sum(s["value"] for s in recorded[UNDER]["series"]
                if s["labels"]["phase"] == phase)
    assert under == pytest.approx(built, rel=1e-6, abs=1e-9)


def test_a_compile_stands_behind_every_program_the_cache_met(recorded):
    cold = {s["labels"]["program"] for s in recorded[COLD]["series"]}
    met = {s["labels"]["program"]
           for s in recorded["cdt_program_cache_total"]["series"]}
    assert cold == met
    # a pool's compiles count each for itself: more than the ledger's wall
    by_program = defaultdict(float)
    for s in recorded[BUILD]["series"]:
        if s["labels"]["phase"] == "compile":
            by_program[s["labels"]["program"]] += s["sum"]
    drawn, = [s["value"] for s in recorded[COLD]["series"]
              if s["labels"]["program"] == "draw_leaf"]
    assert drawn > by_program["draw_leaf"] > 0


def test_the_set_up_table_prints_the_call_sites_with_no_edit(recorded,
                                                             capsys):
    """No bare ``<lambda>``: the ``setup_named_pct`` reader's table names
    the line that called each anonymous program."""
    programs = {s["labels"]["program"] for s in recorded[BUILD]["series"]}
    assert "<lambda>" not in programs
    assert any(p.startswith("<lambda>@flax/core/scope.py:") for p in programs)
    costly = json.loads(json.dumps(recorded))       # at full size they rank
    site = next(s for s in costly[BUILD]["series"]
                if s["labels"]["program"].startswith("<lambda>@flax/"))
    site["sum"] += 5.0
    assert readers.read("setup_named_pct", _ctx(costly)) > 0
    table = capsys.readouterr().out.split("did not serve")[0]
    assert f"  {site['labels']['program']}: trace" in table


@pytest.mark.parametrize("name", ["abstract_pass_s", "cold_compile_s"])
def test_a_program_without_the_families_leaves_the_metric_out(parent, name):
    assert readers.read(name, _ctx(parent)) is None
