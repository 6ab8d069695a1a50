"""The reduction from a trace to busy time, idle share, own time per
operation and labelled gaps: on a trace written by hand, and on the small
one recorded on the chip (``record_fixture.py``)."""

import json
from pathlib import Path

import pytest

from cdtbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                    # nanoseconds
PHASES = {"prep": "prep_body", "denoise": "seg_body", "decode": "fin_body"}


def _trace():
    """One chip, 100 ms traced (a host event spans it). Programs: prep
    0-2, seg 10-40, seg 42-72, fin 75-85 ms. In each seg a `while` op
    spans its fusions; 1 ms inside the second seg is idle."""
    ops = [("copy.1", 0 * MS, 2 * MS),
           ("while.1", 10 * MS, 30 * MS),
           ("fusion.1", 10 * MS, 20 * MS),
           ("_flash_mha_fused.7", 30 * MS, 10 * MS),
           ("while.1", 42 * MS, 30 * MS),
           ("fusion.1", 42 * MS, 19 * MS),
           ("_flash_mha_fused.7", 62 * MS, 10 * MS),
           ("fusion.9", 75 * MS, 10 * MS)]
    modules = [("jit_prep_body(1)", 0 * MS, 2 * MS),
               ("jit_seg_body(2)", 10 * MS, 30 * MS),
               ("jit_seg_body(2)", 42 * MS, 30 * MS),
               ("jit_fin_body(3)", 75 * MS, 10 * MS)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules,
                              "Steps": []},
            "/host:CPU": {"python": [("handler", 0.0, 100 * MS)]}}


def test_busy_is_the_union_and_idle_its_complement():
    reduced = tr.reduce(_trace(), PHASES)
    # 2 + 30 + 30 + 10: the while ops cover their bodies, once
    assert reduced["busy_s"] == pytest.approx(0.072)
    assert reduced["window_s"] == pytest.approx(0.100)
    assert reduced["idle_pct"] == pytest.approx(28.0)
    assert reduced["chips"] == 1


def test_an_operation_keeps_its_own_time_only():
    reduced = tr.reduce(_trace(), PHASES)
    own = reduced["op_seconds"]
    assert own["fusion.1"] == pytest.approx(0.039)
    assert own["_flash_mha_fused.7"] == pytest.approx(0.020)
    # the first while is all children; the second has 1 ms of its own
    assert own["while.1"] == pytest.approx(0.001)
    assert sum(own.values()) == pytest.approx(reduced["busy_s"])
    # the breakdown ranks families: fusion.1 and fusion.9 are one
    assert reduced["device_ops"][0] == ["fusion (x2 ops)",
                                        pytest.approx(0.049)]
    assert tr.short_name("%fusion.7 = bf16[2]{0} fusion(%_flash_mha_fused.3)"
                         ) == "fusion.7"
    assert tr.family("pad.48.clone") == "pad" and tr.family("while") == "while"


def test_shares_match_by_name_and_nothing_matched_is_not_zero():
    reduced = tr.reduce(_trace(), PHASES)
    assert tr.share_pct(reduced, "^_flash_mha") == pytest.approx(
        100 * 0.020 / 0.072)
    assert tr.share_pct(reduced, "all-reduce") is None


def test_gaps_are_labelled_by_the_programs_around_them():
    t = _trace()
    # make the idle millisecond inside the second segment visible: the
    # while op there is a marker, not work
    t["/device:TPU:0"]["XLA Ops"] = [
        e for e in t["/device:TPU:0"]["XLA Ops"] if e[0] != "while.1"]
    dev = t["/device:TPU:0"]
    gaps = dict(tr.label_gaps(dev["XLA Ops"], dev["XLA Modules"], PHASES))
    assert gaps["prep -> denoise"] == pytest.approx(0.008)
    assert gaps["denoise -> denoise"] == pytest.approx(0.002)
    assert gaps["inside denoise"] == pytest.approx(0.001)
    assert gaps["denoise -> decode"] == pytest.approx(0.003)
    reduced = tr.reduce(t, PHASES)
    assert reduced["idle_gaps"][0][0].startswith("prep -> denoise (x1")
    assert reduced["phase_seconds"]["denoise"] == {
        "seconds": pytest.approx(0.060), "count": 2}
    assert tr.phase_of("jit_text_encode(9)", PHASES) == "other:jit_text_encode"


def test_no_device_plane_gives_no_device_number():
    assert tr.reduce({"/host:CPU": {"python": [("x", 0.0, 5.0)]}}) is None


def test_the_mean_is_taken_over_chips():
    t = _trace()
    t["/device:TPU:1"] = {"XLA Ops": [("fusion.1", 0.0, 36 * MS)],
                          "XLA Modules": []}
    reduced = tr.reduce(t, PHASES)
    assert reduced["chips"] == 2
    assert reduced["busy_s"] == pytest.approx((0.072 + 0.036) / 2)


@pytest.mark.skipif(not (DATA / "small.xplane.pb").is_file(),
                    reason="no recorded trace beside the test")
def test_the_recorded_trace_reduces_to_what_the_host_saw():
    expected = json.loads((DATA / "expected.json").read_text())
    trace = tr.load(DATA / "small.xplane.pb")
    reduced = tr.reduce(trace, PHASES)
    assert reduced is not None and reduced["chips"] == 1
    counts = {p: v["count"] for p, v in reduced["phase_seconds"].items()}
    assert counts["prep"] == expected["calls"].count("prep_body")
    assert counts["denoise"] == expected["calls"].count("seg_body")
    assert counts["decode"] == expected["calls"].count("fin_body")
    assert 0 < reduced["busy_s"] < reduced["window_s"] <= \
        expected["traced_s"] * 1.5
    timed = tr.reduce(trace, PHASES, window_s=expected["traced_s"])
    assert timed["window_s"] == expected["traced_s"]
    assert timed["idle_pct"] > reduced["idle_pct"]
    assert 0 < reduced["idle_pct"] < 100
    # the sleeps the host made are idle gaps after the decode program
    slept = sum(expected["sleeps_s"][:-1])
    after_decode = sum(s for label, s in reduced["idle_gaps"]
                       if label.startswith("decode -> prep"))
    assert after_decode >= slept * 0.9
    assert sum(reduced["op_seconds"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)
