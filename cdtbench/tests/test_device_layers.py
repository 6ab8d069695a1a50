"""The device's busy seconds by layer: the wire reader against the recorded
traces, the resolution rules on planes written by hand, the metric
arithmetic, and the answer file a second reader reuses."""

import json
import shutil
import subprocess
from pathlib import Path

import pytest

from cdtbench import device_layers as dl
from cdtbench import readers

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
NEW = ["device_named_pct", "resnet_pct", "attn_proj_pct", "ffn_pct",
       "sampler_pct", "resnet_xla_mxu_pct", "attn_proj_xla_mxu_pct",
       "ffn_xla_mxu_pct", "llm_attn_pct", "llm_experts_pct", "llm_ffn_pct",
       "llm_head_sample_pct"]


# --- the wire format, against traces recorded on the chip ----------------------


@pytest.fixture(scope="module")
def small():
    (plane,) = dl.read_space(DATA / "small.xplane.pb")
    return plane


def test_the_reader_finds_the_device_plane_its_peaks_and_its_lines(small):
    assert small["name"] == "/device:TPU:0"
    assert small["stats"]["peak_teraflops_per_second"] == pytest.approx(202.7)
    assert small["stats"]["peak_hbm_bw_gigabytes_per_second"] == \
        pytest.approx(819.16, abs=0.01)
    assert small["stats"]["device_type_string"] == "TPU v5 Lite"
    assert len(small["lines"]["XLA Ops"]) == 96
    assert len(small["lines"]["XLA Modules"]) == 8
    modules = [small["metadata"][key]["name"].split("(")[0]
               for key, _, _ in small["lines"]["XLA Modules"]]
    expected = json.loads((DATA / "expected.json").read_text())
    assert modules == [f"jit_{name}" for name in expected["calls"]]


def test_an_operations_metadata_carries_name_count_and_category(small):
    (meta,) = [m for m in small["metadata"].values()
               if m["display_name"] == "convolution_tanh_fusion.2"]
    stats = meta["stats"]
    assert stats["tf_op"] == \
        "jit(seg_body)/while/body/closed_call/dot_general:"
    assert stats["model_flops"] == 2_149_580_800
    assert stats["hlo_category"] == "convolution fusion"
    assert stats["source"].endswith("cdtbench/tests/record_fixture.py:31")
    op = dl.describe(meta)
    assert op["name"] == "convolution_tanh_fusion.2"
    assert op["family"] == "convolution_tanh_fusion"
    assert op["flops"] == 2_149_580_800 and op["bytes"] == 6_291_456
    assert op["layer"] == dl.UNNAMED and not op["control_flow"]
    (loop,) = [m for m in small["metadata"].values()
               if m["display_name"] == "while"]
    assert dl.describe(loop)["control_flow"]


def test_tensorflows_own_reader_agrees(small):
    """A cross-check where tensorflow happens to be installed: the wire
    reader and ``xplane_pb2`` see the same events and the same stats."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    space.ParseFromString((DATA / "small.xplane.pb").read_bytes())
    (plane,) = [p for p in space.planes if p.name == "/device:TPU:0"]
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    for line in plane.lines:
        if line.name in small["lines"]:
            assert small["lines"][line.name] == [
                (e.metadata_id, line.timestamp_ns * 1000 + e.offset_ps,
                 e.duration_ps) for e in line.events]
    assert set(small["metadata"]) == set(plane.event_metadata)
    for key, meta in plane.event_metadata.items():
        mine = small["metadata"][key]
        assert mine["name"] == meta.name
        assert mine["display_name"] == meta.display_name
        for stat in meta.stats:
            kind = stat.WhichOneof("value")
            value = getattr(stat, kind)
            if kind == "ref_value":
                value = names[value]
            assert mine["stats"][names[stat.metadata_id]] == value


def test_the_seconds_sum_to_the_reductions_busy_time(small):
    """Self times leave nothing out and count nothing twice: their sum is
    the union ``trace_reduce`` calls busy (here computed from the same
    events, without JAX)."""
    from cdtbench.stats import union_seconds

    chip = dl.chip_report(small, {"denoise": "seg_body"})
    busy = union_seconds((s, s + d) for _, s, d in
                         small["lines"]["XLA Ops"]) * dl.PS
    assert chip["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert chip["events"] == 96
    # no scope in that program: everything is unnamed, and the report
    # says there is nothing to read rather than 0%
    assert set(chip["layers"]) == {dl.UNNAMED}
    assert dl.report([small], {}) is None
    # the scan's while adds its own seconds and nothing else
    row = chip["layers"][dl.UNNAMED]
    assert row["ops"] == 96 - 4                 # two calls of two programs
    assert row["flops"] == sum(
        dl.describe(small["metadata"][key])["flops"]
        for key, _, _ in small["lines"]["XLA Ops"]
        if not dl.describe(small["metadata"][key])["control_flow"])
    assert chip["phases"]["denoise"][dl.UNNAMED]["seconds"] > \
        0.9 * chip["busy_s"]


def test_the_recorded_layers_fixture_reads_as_when_it_was_recorded():
    """A toy program with two scopes and one unscoped operation, recorded
    on the chip by ``record_layers_fixture.py``."""
    expected = json.loads((DATA / "layers.expected.json").read_text())
    answer = dl.report(dl.read_space(DATA / "layers.xplane.pb"),
                       {"toy": "toy_body"})
    assert set(answer["layers"]) == {"ffn", "norm_mod", dl.UNNAMED}
    assert answer["chips"] == 1
    for layer, seconds in expected["seconds"].items():
        assert answer["layers"][layer]["seconds"] == pytest.approx(seconds)
        assert answer["layers"][layer]["ops"] == expected["ops"][layer]
    assert answer["named_pct"] == pytest.approx(expected["named_pct"])
    assert sum(r["seconds"] for r in answer["layers"].values()) == \
        pytest.approx(answer["busy_s"])
    # what the program is known to do: the product runs once a step under
    # cdt.ffn, and the compiler counts at least its 2·N³ operations there
    steps = expected["calls"] * expected["steps"]
    ffn = answer["layers"]["ffn"]
    assert ffn["flops"] >= steps * expected["product_flops"]
    assert ffn["flops"] < 1.02 * steps * expected["product_flops"]
    mxu = dl.xla_mxu_pct(answer, "ffn", 197e12)
    assert 50.0 < mxu <= 100.0
    assert answer["layers"][dl.UNNAMED]["seconds"] > 0
    assert answer["phases"]["toy"]["ffn"]["seconds"] == \
        pytest.approx(ffn["seconds"])
    assert dl.share_pct(answer, ["ffn", "norm_mod"]) == \
        pytest.approx(answer["named_pct"])
    text = "\n".join(dl.lines(answer))
    assert "cdt.ffn" in text and "record_layers_fixture.py" in text


# --- the resolution rules, on planes written by hand ---------------------------

US = 1_000_000                              # picoseconds


def _plane(name="/device:TPU:0", scale=1):
    """One program of 100 µs: a ``while`` (10–90) holding a product under
    two nested scopes (10–50), a loop fusion under one (50–70) and an
    unscoped copy (70–85): 5 µs of the loop are its own. Before it an
    unscoped operation (0–10), after it a Pallas call under a scope, with
    no count (90–100)."""
    def meta(name, category, tf_op, flops=0, bytes_=0, source=""):
        return {"name": f"%{name} = f32[] thing()", "display_name": name,
                "stats": {"hlo_category": category, "tf_op": tf_op,
                          "model_flops": flops, "flops": flops,
                          "bytes_accessed": bytes_, "source": source,
                          "program_id": 7}}
    metadata = {
        1: meta("copy.1", "data formatting", "jit(f)/transpose:"),
        2: meta("while", "while", "jit(f)/cdt.sampler/while:", 999, 999),
        3: meta("convolution_fusion.3", "convolution fusion",
                "jit(f)/cdt.sampler/while/body/cdt.ffn/proj/dot_general:",
                4_000_000_000, 1000, "layers.py:10"),
        4: meta("fusion.4", "loop fusion",
                "jit(f)/while/body/vmap(cdt.norm_mod)/mul:", 2000, 4000,
                "layers.py:20"),
        5: meta("copy.5", "data formatting", "jit(f)/while/body/copy:"),
        6: meta("_flash.6", "custom-call",
                "jit(f)/cdt.attn_core/pallas_call:", 0, 0, "attention.py:5"),
        9: {"name": "jit_f(7)", "display_name": "", "stats": {}},
    }
    ops = [(1, 0, 10), (2, 10, 80), (3, 10, 40), (4, 50, 20), (5, 70, 15),
           (6, 90, 10)]
    return {"name": name, "metadata": metadata, "stats": {},
            "lines": {"XLA Ops": [(k, round(s * US * scale),
                                   round(d * US * scale))
                                  for k, s, d in ops],
                      "XLA Modules": [(9, 0, 100 * US)]}}


def test_innermost_scope_wins_and_control_flow_carries_no_work():
    answer = dl.report([_plane()], {"step": "jit_f"})
    rows = answer["layers"]
    assert rows["ffn"]["seconds"] == pytest.approx(40e-6)   # not sampler's
    assert rows["norm_mod"]["seconds"] == pytest.approx(20e-6)   # vmap(...)
    assert rows["attn_core"]["seconds"] == pytest.approx(10e-6)
    # the while: its own 5 µs where its own name resolves, and none of
    # its count (the children carry the operations)
    assert rows["sampler"] == {
        "seconds": pytest.approx(5e-6), "adopted_seconds": 0.0, "ops": 0,
        "flops": 0, "bytes": 0,
        "counted_flops": 0, "counted_seconds": 0.0,
        "tflops_per_s": 0.0, "gb_per_s": 0.0}
    assert rows[dl.UNNAMED]["seconds"] == pytest.approx(25e-6)
    assert rows[dl.UNNAMED]["ops"] == 2
    assert answer["busy_s"] == pytest.approx(100e-6)
    assert answer["named_pct"] == pytest.approx(75.0)
    assert rows["ffn"]["tflops_per_s"] == pytest.approx(100.0)
    assert rows["norm_mod"]["gb_per_s"] == pytest.approx(0.2)
    assert answer["phases"]["step"]["ffn"]["flops"] == 4_000_000_000
    assert answer["categories"]["ffn"] == {
        "convolution fusion": {"seconds": pytest.approx(40e-6),
                               "flops": 4_000_000_000}}
    # the costliest rows name the instruction and its source line; the
    # unnamed ones their place and family
    assert answer["top"]["ffn"][0]["at"] == [
        "jit(f)/cdt.sampler/while/body/cdt.ffn/proj/dot_general",
        "layers.py:10"]
    assert sorted(row["at"] for row in answer["top"][dl.UNNAMED]) == [
        ["jit(f)/transpose", "copy"], ["jit(f)/while/body/copy", "copy"]]


def _prefetching_plane():
    """Two programs whose compiler brought operands in ahead of their use:
    operations with no ``tf_op`` at all, named only by what reads them."""
    def meta(text, category, tf_op, program):
        return {"name": text, "display_name": text.split(" = ")[0][1:],
                "stats": {"hlo_category": category, "tf_op": tf_op,
                          "program_id": program}}
    metadata = {
        1: meta("%copy-start.1 = (bf16[8]{0:S(1)}, bf16[8]{0}, u32[]) "
                "copy-start(bf16[8]{0} %w)", "copy-start", "", 7),
        2: meta("%copy-done.1 = bf16[8]{0:S(1)} copy-done((bf16[8]{0:S(1)}, "
                "bf16[8]{0}, u32[]) %copy-start.1)", "copy-done", "", 7),
        3: meta("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0:S(1)} %copy-done.1,"
                " bf16[8]{0} %x), kind=kLoop, calls=%fused_computation.2",
                "loop fusion", "jit(f)/cdt.llm_attn/mul:", 7),
        # read by an operation the program traced and left unscoped
        4: meta("%slice-done.3 = bf16[8]{0} async-done(%slice-start.3)",
                "async-done", "", 7),
        5: meta("%fusion.4 = bf16[8]{0} fusion(%slice-done.3), kind=kLoop",
                "loop fusion", "jit(f)/mul:", 7),
        # read from several layers: where most readers are
        6: meta("%copy.5 = bf16[8]{1,0} copy(bf16[8]{0,1} %p)",
                "data formatting", "", 7),
        7: meta("%fusion.6 = f32[] fusion(%copy.5, %slice-done.7)",
                "loop fusion", "jit(f)/cdt.llm_experts/dot_general:", 7),
        8: meta("%fusion.7 = f32[] fusion(%copy.5)", "loop fusion",
                "jit(f)/cdt.llm_router/dot_general:", 7),
        9: meta("%fusion.8 = f32[] fusion(%fusion.7, %copy.5)", "loop fusion",
                "jit(f)/cdt.llm_router/top_k:", 7),
        # read by nothing the trace shows (it leaves through the tuple)
        10: meta("%copy-done.9 = bf16[8]{0} copy-done(%copy-start.9)",
                 "copy-done", "", 7),
        # another program's instruction of the same name
        11: meta("%copy-done.1 = bf16[8]{0:S(1)} copy-done(%copy-start.1)",
                 "copy-done", "", 8),
        12: meta("%fusion.2 = bf16[8]{0} fusion(%copy-done.1)",
                 "loop fusion", "jit(g)/cdt.ffn/mul:", 8),
        # named after the loop it was hoisted for, not after a primitive
        13: meta("%slice-done.7 = bf16[8]{0:S(1)} async-done(%slice-start.7)",
                 "async-done", "jit(f)/while:", 7),
        20: {"name": "jit_f(7)", "display_name": "", "stats": {}},
        21: {"name": "jit_g(8)", "display_name": "", "stats": {}},
    }
    ops = [(key, 10 * i, 10) for i, key in enumerate(range(1, 14))]
    return {"name": "/device:TPU:0", "metadata": metadata, "stats": {},
            "lines": {"XLA Ops": [(k, s * US, d * US) for k, s, d in ops],
                      "XLA Modules": [(20, 0, 100 * US),
                                      (21, 100 * US, 20 * US)]}}


def test_the_compilers_own_operations_go_with_what_reads_them():
    answer = dl.report([_prefetching_plane()], {"f": "jit_f", "g": "jit_g"})
    rows = answer["layers"]
    # the copy, its wait and the fusion that reads it: one layer, and the
    # row says how much of it came through a reader
    assert rows["llm_attn"]["seconds"] == pytest.approx(30e-6)
    assert rows["llm_attn"]["adopted_seconds"] == pytest.approx(20e-6)
    assert rows["llm_attn"]["ops"] == 3
    # two of the copy's three readers are the router's
    assert rows["llm_router"]["seconds"] == pytest.approx(30e-6)
    assert rows["llm_router"]["adopted_seconds"] == pytest.approx(10e-6)
    # an operation that carries its loop's name and no primitive's is the
    # compiler's too
    assert rows["llm_experts"]["adopted_seconds"] == pytest.approx(10e-6)
    assert dl.compilers_own(dl.describe(
        _prefetching_plane()["metadata"][13]))
    assert not dl.compilers_own(dl.describe(
        _prefetching_plane()["metadata"][5]))
    # the other program's copy-done.1 is its own program's
    assert rows["ffn"]["seconds"] == pytest.approx(20e-6)
    assert answer["phases"]["g"]["ffn"]["adopted_seconds"] == \
        pytest.approx(10e-6)
    # what the program traced outside every scope stays unnamed, with the
    # wait it caused; so does what nothing named reads
    assert rows[dl.UNNAMED]["seconds"] == pytest.approx(30e-6)
    assert rows[dl.UNNAMED]["adopted_seconds"] == 0.0
    assert answer["adopted_s"] == pytest.approx(50e-6)
    assert answer["named_pct"] == pytest.approx(100 * 100 / 130)
    assert ["(the compiler's copy-done, for what reads it)", ""] in [
        row["at"] for row in answer["top"]["llm_attn"]]
    assert "adopted 0.000020 s" in "\n".join(dl.lines(answer))


def test_a_layer_that_matched_nothing_reads_none_never_zero():
    answer = dl.report([_plane()], {"step": "jit_f"})
    assert dl.share_pct(answer, ["ffn"]) == pytest.approx(40.0)
    assert dl.share_pct(answer, ["ffn", "norm_mod"]) == pytest.approx(60.0)
    assert dl.share_pct(answer, ["resnet"]) is None
    assert dl.share_pct(answer, ["resnet", "ffn"]) == pytest.approx(40.0)
    assert dl.share_pct(None, ["ffn"]) is None
    # shares of some programs' seconds only
    assert dl.share_pct(answer, ["ffn"], ["step"]) == pytest.approx(40.0)
    assert dl.share_pct(answer, ["ffn"], ["llm_decode"]) is None
    # the compiler's count over the counted operations' own seconds
    assert dl.xla_mxu_pct(answer, "ffn", 200e12) == pytest.approx(50.0)
    assert dl.xla_mxu_pct(answer, "attn_core", 200e12) is None   # no count
    assert dl.xla_mxu_pct(answer, "resnet", 200e12) is None
    assert dl.xla_mxu_pct(None, "ffn", 200e12) is None
    assert dl.layer_of("jit(f)/closed_call/dot_general") == dl.UNNAMED
    assert dl.layer_of(None) == dl.UNNAMED


def test_several_chips_are_meant_and_their_spread_is_said():
    slow = _plane("/device:TPU:1", scale=1.1)
    answer = dl.report([_plane(), slow], {})
    assert answer["chips"] == 2
    assert [c["plane"] for c in answer["per_chip"]] == [
        "/device:TPU:0", "/device:TPU:1"]
    assert answer["layers"]["ffn"]["seconds"] == pytest.approx(42e-6)
    assert answer["spread_pct"]["ffn"] == pytest.approx(100 * 4 / 42)
    assert answer["per_chip"][1]["seconds"]["ffn"] == pytest.approx(44e-6)
    assert answer["layers"]["ffn"]["ops"] == 1
    # a program with no scope anywhere: nothing to read, on any chip
    bare = _plane()
    for meta in bare["metadata"].values():
        if "tf_op" in meta["stats"]:
            meta["stats"]["tf_op"] = meta["stats"]["tf_op"].replace(
                "cdt.", "")
    assert dl.report([bare], {}) is None


# --- once a traced run -----------------------------------------------------------


def _profile(tmp_path, fixture="layers.xplane.pb"):
    at = tmp_path / "profile" / "plugins" / "profile" / "2026_09_29"
    at.mkdir(parents=True)
    shutil.copy(DATA / fixture, at / "host.xplane.pb")
    return tmp_path / "profile"


def test_a_second_reader_takes_the_answer_from_the_file(tmp_path,
                                                        monkeypatch):
    profile, kept = _profile(tmp_path), tmp_path / dl.ANSWER_NAME
    calls, said = [], []
    real = subprocess.run

    def counted(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(subprocess, "run", counted)
    first = dl.run(profile, kept, {"toy": "toy_body"}, said.append)
    assert first["layers"]["ffn"]["seconds"] > 0 and len(calls) == 1
    assert any("cdt.<layer>" in line for line in said)
    again = dl.run(profile, kept, {"toy": "toy_body"}, said.append)
    assert again == first and len(calls) == 1
    # another trace (a later run in the same directory) is read anew
    shutil.rmtree(profile)
    _profile(tmp_path, "small.xplane.pb")
    assert dl.run(profile, kept, {}, said.append) is None
    assert len(calls) == 2
    # and "nothing to read" is an answer too: nobody reads it twice
    assert dl.run(profile, kept, {}, said.append) is None
    assert len(calls) == 2


def test_a_reader_that_does_not_end_is_not_waited_for_twice(tmp_path,
                                                            monkeypatch):
    profile = _profile(tmp_path, "small.xplane.pb")
    kept = tmp_path / dl.ANSWER_NAME
    calls, said = [], []

    def never(*args, **kwargs):
        calls.append(args)
        raise subprocess.TimeoutExpired(args[0], kwargs["timeout"])

    monkeypatch.setattr(subprocess, "run", never)
    assert dl.run(profile, kept, {}, said.append) is None
    assert dl.run(profile, kept, {}, said.append) is None
    assert len(calls) == 1 and "not read inside" in said[0]
    assert dl.run(tmp_path / "nowhere", kept, {}, said.append) is None


# --- the metric files ------------------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_has_its_files_and_reads_nothing_untraced(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "request_p50_s" and entry["workloads"]
    spec = readers.spec_of(name)
    assert spec["reader"] == "python" and spec["unit"] == entry["unit"]
    assert (readers.HERE / f"{name}.py").is_file()
    # an untraced run (and a CPU rehearsal) opens no trace and reads None
    assert readers.read(name, {"trace": None}) is None


def test_the_metric_files_read_the_report_they_are_given(monkeypatch):
    answer = dl.report([_plane()], {"step": "jit_f"})
    monkeypatch.setattr(dl, "of_run", lambda ctx: answer)
    ctx = {"trace": {}, "device": {"kind": "TPU v5 lite"}}
    assert readers.read("device_named_pct", ctx) == pytest.approx(75.0)
    assert readers.read("ffn_pct", ctx) == pytest.approx(40.0)
    assert readers.read("resnet_pct", ctx) is None
    assert readers.read("ffn_xla_mxu_pct", ctx) == \
        pytest.approx(100 * 100e12 / 197e12)
    assert readers.read("llm_attn_pct", ctx) is None     # no such program
