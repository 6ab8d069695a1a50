"""Idle seconds named by host span: the arithmetic on a trace written by
hand (a gap inside one span, a gap across two, a gap in none), and the
reader's answer where there is nothing to read."""

import json
from pathlib import Path

import pytest

from cdtbench import host_spans as hs
from cdtbench import readers

MS = 1e6                                    # nanoseconds


def _busy():
    """One chip. Programs run 10-40, 60-90 and 100-130 ms; inside the
    first, two operations leave 1 ms idle at 20-21."""
    return [(10 * MS, 20 * MS), (21 * MS, 40 * MS),
            (12 * MS, 18 * MS),             # nested in the first: no gap
            (60 * MS, 90 * MS), (100 * MS, 130 * MS)]


def _spans():
    """The sampler node 0-140 ms, on one thread: launch 0-10, wait 10-42,
    boundary 42-50, launch 50-58, wait 58-92, wait 99-131 (7 ms of the
    node between the last two are under no inner span). A progress
    callback runs 19-22 on another thread. A second node 150-160."""
    return [("node.TPUTxt2Img", 0 * MS, 140 * MS),
            ("program.launch", 0 * MS, 10 * MS),
            ("program.wait", 10 * MS, 42 * MS),
            ("segment.boundary", 42 * MS, 50 * MS),
            ("program.launch", 50 * MS, 58 * MS),
            ("program.wait", 58 * MS, 92 * MS),
            ("program.wait", 99 * MS, 131 * MS),
            ("progress.sink", 19 * MS, 22 * MS),
            ("node.SaveImage", 150 * MS, 160 * MS)]


def test_every_idle_instant_goes_to_the_span_opened_last():
    got = hs.attribute(_busy(), _spans())
    by = got["by_span"]
    assert got["window_s"] == pytest.approx(0.160)
    # idle: 0-10, 20-21, 40-60, 90-100, 130-160
    assert got["idle_s"] == pytest.approx(0.071)
    # a gap inside one span: the launch before the first operation
    # (0-10), and 50-58 of the gap that crosses three
    assert by["program.launch"] == pytest.approx(0.018)
    # the callback opened after the wait that it runs beside: 20-21
    assert by["progress.sink"] == pytest.approx(0.001)
    # a gap across spans is split where they change: 40-42 and 58-60 of
    # the long one, 90-92 and 99-100 around the hole, 130-131
    assert by["program.wait"] == pytest.approx(0.008)
    assert by["segment.boundary"] == pytest.approx(0.008)
    # a gap in no inner span stays with the node: 92-99 and 131-140
    assert by["node.TPUTxt2Img"] == pytest.approx(0.016)
    assert by["node.SaveImage"] == pytest.approx(0.010)
    # and between two nodes with nothing at all: 140-150
    assert by[hs.NO_SPAN] == pytest.approx(0.010)
    assert sum(by.values()) == pytest.approx(got["idle_s"])
    assert got["named_s"] == pytest.approx(0.035)
    assert got["idle_named_pct"] == pytest.approx(100 * 0.035 / 0.071)


def test_the_long_gaps_are_listed_with_their_split():
    got = hs.attribute(_busy(), _spans())
    gaps = {round(g["at_s"], 3): g for g in got["large_gaps"]}
    assert sorted(gaps) == [0.0, 0.04, 0.09, 0.13]      # 20-21 is short
    across = gaps[0.04]
    assert across["seconds"] == pytest.approx(0.020)
    assert across["by_span"] == {
        "program.wait": pytest.approx(0.004),
        "segment.boundary": pytest.approx(0.008),
        "program.launch": pytest.approx(0.008)}
    assert gaps[0.09]["by_span"] == {
        "program.wait": pytest.approx(0.003),
        "node.TPUTxt2Img": pytest.approx(0.007)}
    text = "\n".join(hs.lines(got))
    assert "segment.boundary 0.008000" in text and "idle  0.018000 s" in text


def test_nothing_to_read_is_none_not_zero(tmp_path):
    # the parent of PR 24 mirrors no span; a CPU trace has no device plane
    assert hs.attribute(_busy(), []) is None
    assert hs.attribute([], _spans()) is None
    # a chip that was never idle has no share to name
    assert hs.attribute([(0.0, 10 * MS)], [("program.wait", 1 * MS, 9 * MS)]
                        )["idle_named_pct"] is None
    # no profile under the directory: the subprocess answers None, the
    # reader says so and the metric is left out
    said = []
    assert hs.run(tmp_path, tmp_path / "answer.json", said.append) is None
    assert json.loads((tmp_path / "answer.json").read_text()) is None
    assert "nothing is read" in said[0]
    assert readers.read("idle_named_pct", {"trace": None}) is None


def test_a_trace_without_the_prefix_is_answered_unwalked(tmp_path,
                                                         monkeypatch):
    # the trace recorded on the chip at PR 23: device planes, no cdt.*
    recorded = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
    (tmp_path / "small.xplane.pb").write_bytes(recorded.read_bytes())
    monkeypatch.setattr(hs, "load", lambda path: pytest.fail("walked"))
    assert hs.main([str(tmp_path), str(tmp_path / "answer.json")]) == 0
    assert json.loads((tmp_path / "answer.json").read_text()) is None
