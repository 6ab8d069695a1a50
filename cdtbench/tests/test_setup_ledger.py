"""The eight set-up readers against a recorded ``metrics.json`` snapshot
(``data/setup_ledger.metrics.json``: a CPU rehearsal of
``sd3-medium.solo28``, cut to the families they read, taken when the run
closed, its steady calls cut back to the one a program that the second
warm-up makes) and against a snapshot of a program without the series."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from cdtbench import readers

DATA = Path(__file__).resolve().parent / "data"
SETUP_S = 15.31          # what the recorded run's client measured
NEW = ("boot_s", "weights_s", "trace_s", "lower_s", "cache_read_s",
       "miss_compile_s", "first_run_s")


def _ctx(opened, setup_s=SETUP_S):
    return {"cell": SimpleNamespace(config={}, name="recorded"),
            "opened": opened, "closed": opened, "setup_s": setup_s}


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "setup_ledger.metrics.json").read_text())


def _sum(snapshot, name, phases=None):
    return sum(s.get("sum", s.get("value", 0.0))
               for s in snapshot[name]["series"]
               if phases is None or s["labels"]["phase"] in phases)


@pytest.mark.parametrize("name, series, phases", [
    ("boot_s", "cdt_boot_seconds", None),
    ("weights_s", "cdt_weights_seconds", None),
    ("trace_s", "cdt_program_build_seconds", ("trace",)),
    ("lower_s", "cdt_program_build_seconds", ("lower",)),
    ("cache_read_s", "cdt_program_build_seconds",
     ("cache_key", "cache_read")),
    ("miss_compile_s", "cdt_program_build_seconds", ("compile",)),
    ("first_run_s", "cdt_program_build_seconds", ("first_run",)),
])
def test_a_data_file_reads_its_phases(recorded, name, series, phases):
    spec = readers.spec_of(name)
    if name != "cache_read_s":          # a reader of its own: see below
        assert spec["reader"] == "histogram" and spec["over"] == "open"
    value = readers.read(name, _ctx(recorded))
    assert value == pytest.approx(_sum(recorded, series, phases))
    assert value > 0


def test_the_backend_phases_are_compile_s(recorded):
    ctx = _ctx(recorded)
    assert (readers.read("cache_read_s", ctx)
            + readers.read("miss_compile_s", ctx)) == pytest.approx(
        readers.read("compile_s", ctx), rel=1e-9)


def test_a_run_that_read_nothing_reports_zero_seconds_of_reads(recorded):
    """A checkout's first run compiles everything: 0 s of reads is a
    reading, and every cell has to report the metric."""
    cold = json.loads(json.dumps(recorded))
    build = cold["cdt_program_build_seconds"]
    build["series"] = [s for s in build["series"]
                       if not s["labels"]["phase"].startswith("cache_")]
    assert readers.read("cache_read_s", _ctx(cold)) == 0.0
    assert readers.read("miss_compile_s", _ctx(cold)) > 0


def test_setup_named_pct_is_a_share_and_lists_the_unserved(recorded,
                                                           capsys):
    value = readers.read("setup_named_pct", _ctx(recorded))
    parts = sum(readers.read(n, _ctx(recorded)) for n in NEW) + _sum(
        recorded, "cdt_pipeline_execute_seconds")
    assert value == pytest.approx(100.0 * parts / SETUP_S)
    assert 0 < value <= 100.0
    said = capsys.readouterr().out
    assert "under no name" in said and "flow_seg_body" in said
    unserved = said.split("did not serve")[1]
    for s in recorded["cdt_program_cache_total"]["series"]:
        if s["labels"]["outcome"] != "hit":
            assert f" {s['labels']['program']}:" in unserved


@pytest.mark.parametrize("name", NEW + ("setup_named_pct",))
def test_a_program_without_the_series_leaves_the_metric_out(recorded, name):
    parent = {k: v for k, v in recorded.items()
              if k in ("cdt_xla_compile_seconds",
                       "cdt_pipeline_execute_seconds")}
    assert readers.read(name, _ctx(parent)) is None
