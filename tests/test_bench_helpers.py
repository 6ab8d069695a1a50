"""Unit tests for bench.py's shared offload-bench helpers (r04: the
leak budget and two-point extrapolation previously lived as diverging
copies in the flux and wan14b benches), the peaks table, and the rule
that a benchmark without a chip is an error. The compile-cache cases
live in tests/test_chip_smoke.py."""

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location("bench", ROOT / "bench.py")
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


class TestMfuFields:
    """r05: every workload artifact carries mfu (VERDICT r04 weak #1) —
    the shared accounting helper."""

    def test_no_flops_yields_empty(self):
        assert bench._mfu_fields(None, 1.0, True) == {}
        assert bench._mfu_fields(0, 1.0, True) == {}

    def test_cpu_reports_flops_without_mfu(self):
        out = bench._mfu_fields(2e9, 0.5, on_accel=False)
        assert out["model_flops_per_chip"] == 2e9
        assert out["flops_source"] == "analytic_jaxpr"
        assert "mfu" not in out

    def test_mfu_math(self, monkeypatch):
        monkeypatch.setattr(bench, "_peak_flops", lambda kind: 100e12)
        # 50 TFLOP of work in 1 s on a 100 TFLOP/s chip = 0.5 MFU
        out = bench._mfu_fields(50e12, 1.0, on_accel=True)
        assert out["mfu"] == pytest.approx(0.5)
        assert out["peak_flops_per_chip_bf16"] == 100e12

    def test_analytic_flops_counts_bound_fn(self):
        import jax
        import jax.numpy as jnp

        w = jnp.ones((8, 8))

        def jitted(weights, x):
            return x @ weights

        fn = lambda x: jitted(w, x)
        fn.jitted = jitted
        fn.weights = w
        got = bench._analytic_flops(fn, jnp.ones((4, 8)))
        assert got == 2 * 4 * 8 * 8

    def test_analytic_flops_failure_returns_none(self):
        fn = lambda: None
        fn.jitted = lambda *a: (_ for _ in ()).throw(RuntimeError("boom"))
        fn.weights = None
        assert bench._analytic_flops(fn) is None


class TestExtrapolateSteps:
    def test_linear_two_point(self):
        # 2 steps -> 10 s, 6 steps -> 22 s: 3 s/step + 4 s overhead
        median, per_step, d = bench._extrapolate_steps(10.0, 2, 22.0, 6,
                                                       30)
        assert per_step == pytest.approx(3.0)
        assert median == pytest.approx(4.0 + 3.0 * 30)
        assert d["derived"] and d["measured_steps"] == [2, 6]
        assert d["fixed_overhead_s"] == pytest.approx(4.0)

    def test_degenerate_single_point_is_conservative(self):
        median, per_step, d = bench._extrapolate_steps(10.0, 2, 10.0, 2,
                                                       30)
        assert per_step == pytest.approx(5.0)   # overhead folded in
        assert median == pytest.approx(150.0)

    def test_overhead_never_negative(self):
        _, per_step, d = bench._extrapolate_steps(1.0, 1, 10.0, 2, 30)
        assert d["fixed_overhead_s"] == 0.0
        assert per_step == pytest.approx(9.0)


class TestAffordableForwards:
    def test_no_leak_is_unbounded(self):
        assert bench._affordable_forwards_or_raise(
            0.0, 10 ** 9, 10 ** 9, 100.0) == float("inf")

    def test_upload_alone_can_refuse(self, monkeypatch):
        monkeypatch.setattr(bench, "_mem_available_gb", lambda: 20.0)
        with pytest.raises(RuntimeError, match="upload"):
            bench._affordable_forwards_or_raise(
                1.0, int(4e9), int(12e9), 1.0)

    def test_streamed_budget(self, monkeypatch):
        monkeypatch.setattr(bench, "_mem_available_gb", lambda: 100.0)
        # headroom 100-12-4=84; upload 12*2=24; (84-24)/2 = 30 forwards
        fwds = bench._affordable_forwards_or_raise(
            1.0, int(4e9), int(12e9), 2.0)
        assert fwds == pytest.approx(30.0)

    def test_fewer_than_two_forwards_refuses(self, monkeypatch):
        monkeypatch.setattr(bench, "_mem_available_gb", lambda: 40.0)
        with pytest.raises(RuntimeError, match="fewer than 2"):
            bench._affordable_forwards_or_raise(
                1.0, int(4e9), int(12e9), 20.0)

    def test_fully_resident_streams_nothing(self, monkeypatch):
        monkeypatch.setattr(bench, "_mem_available_gb", lambda: 100.0)
        assert bench._affordable_forwards_or_raise(
            1.0, int(4e9), int(12e9), 0.0) == float("inf")


@pytest.mark.slow
class TestWorkloadsRunOnCpu:
    """Every bench workload's CPU tiny path must produce a valid result
    line end-to-end — the guard that would have caught the r04 registry
    typo before it reached the chip."""

    @pytest.mark.parametrize("workload", sorted(bench._WORKLOADS))
    def test_workload_emits_valid_result(self, workload, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        result = bench._workload_fn(workload)(2, 1)
        assert result["metric"]
        assert result["value"] > 0
        assert result["unit"]
        assert result["platform"] == "cpu"

    def test_registry_covers_cli_choices(self):
        """The argparse choices and the dispatch registry must agree
        (anchored to the --workload argument so other choices= lists
        can't be matched by mistake)."""
        import re

        src = (ROOT / "bench.py").read_text()
        m = re.search(r'"--workload",\s*choices=\[([^]]+)\]', src)
        assert m is not None, "--workload choices list not found"
        choices = set(re.findall(r'["\'](\w+)["\']', m.group(1)))
        assert choices == set(bench._WORKLOADS)


class TestNoChipIsAnError:
    """bench.py is one process on one backend: with no TPU it exits
    non-zero and prints no result, unless the toy-shape CPU dry run was
    asked for with JAX_PLATFORMS=cpu (what TestWorkloadsRunOnCpu does).
    The seven rounds of ``*_cpu`` "results" came from a fallback."""

    def test_exits_nonzero_when_platform_is_not_tpu(self, monkeypatch,
                                                    capsys):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr("sys.argv", ["bench.py", "--steps", "2"])
        monkeypatch.setitem(bench._WORKLOADS, "txt2img",
                            lambda *a: pytest.fail("workload ran"))
        with pytest.raises(SystemExit) as exc:
            bench.main()            # the suite's backend is the CPU
        assert exc.value.code not in (0, None)
        assert "no TPU" in str(exc.value.code)
        assert capsys.readouterr().out == ""     # no result line

    def test_unknown_device_kind_has_no_peak(self):
        assert bench._peak_flops("TPU v5 lite") == 197e12
        with pytest.raises(ValueError, match="no bf16 peak"):
            bench._peak_flops("cpu")
        # ... and MFU against it is an error, not a blank field
        with pytest.raises(ValueError, match="no bf16 peak"):
            bench._mfu_fields(1e12, 1.0, on_accel=True)
