"""cdtlint framework tests (ISSUE 12, docs/lint.md).

Four layers:

- per-rule fixture-snippet matrix (positive + negative + suppression) so
  every rule's detection logic is pinned independently of the repo;
- baseline semantics (new/stale/unjustified; the baseline only shrinks);
- the tier-1 gate: the REAL package lints clean against the committed
  baseline, every baseline entry is justified, docs/knobs.md is
  regeneration-clean, and seeded violations ARE caught (the linter can't
  silently rot into a yes-machine);
- the knob registry and the runtime lock-order detector (a real
  two-thread inversion must be detected; a consistent order must not).
"""

import json
import textwrap
import threading
from pathlib import Path

import pytest

from comfyui_distributed_tpu.lint import lockorder
from comfyui_distributed_tpu.lint.core import (apply_baseline, load_baseline,
                                               run_lint, write_baseline)
from comfyui_distributed_tpu.lint.rules import ALL_RULES, rule_by_id
from comfyui_distributed_tpu.utils import constants

PKG_ROOT = Path(__file__).resolve().parents[1] / "comfyui_distributed_tpu"
REPO_ROOT = PKG_ROOT.parent


def lint_snippet(tmp_path, source, rules=None, name="snippet.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source), encoding="utf-8")
    return run_lint([f], rules or ALL_RULES, tmp_path)


# ---------------------------------------------------------------------------
# L001 lock discipline


class TestL001:
    GOOD = """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._data = {}

            def put(self, k, v):
                with self._lock:
                    self._data[k] = v

            def _grow_locked(self, k):
                self._data[k] = 1      # caller holds the lock (suffix)
        """

    BAD = """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._data = {}

            def put(self, k, v):
                with self._lock:
                    self._data[k] = v

            def racy(self, k):
                self._data[k] = 2      # guarded attr, no lock
                self._data.pop(k)      # mutating method call, no lock
        """

    def test_mutation_outside_lock_flagged(self, tmp_path):
        found = lint_snippet(tmp_path, self.BAD, [rule_by_id("L001")])
        assert len(found) == 2
        assert all(f.rule == "L001" for f in found)
        assert "racy" in found[0].message

    def test_clean_class_and_locked_suffix_pass(self, tmp_path):
        assert lint_snippet(tmp_path, self.GOOD, [rule_by_id("L001")]) == []

    def test_init_exempt_and_unguarded_attr_ignored(self, tmp_path):
        src = """
            import threading

            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._data = {}          # construction: exempt

                def read_path(self):
                    self._scratch = []       # never mutated under lock

                def put(self, k):
                    with self._lock:
                        self._data[k] = 1
            """
        assert lint_snippet(tmp_path, src, [rule_by_id("L001")]) == []

    def test_suppression_comment(self, tmp_path):
        src = self.BAD.replace(
            "self._data[k] = 2      # guarded attr, no lock",
            "self._data[k] = 2  # cdtlint: disable=L001 -- single-writer")
        found = lint_snippet(tmp_path, src, [rule_by_id("L001")])
        assert len(found) == 1          # only the .pop() remains


# ---------------------------------------------------------------------------
# A001 async hygiene


class TestA001:
    def test_blocking_calls_flagged(self, tmp_path):
        src = """
            import subprocess
            import time
            from time import sleep

            async def handler(fut):
                time.sleep(1)
                sleep(2)
                subprocess.run(["ls"])
                open("f").read()
                fut.result()
            """
        found = lint_snippet(tmp_path, src, [rule_by_id("A001")])
        assert len(found) == 5

    def test_sync_def_and_nested_def_exempt(self, tmp_path):
        src = """
            import time

            def sync_fn():
                time.sleep(1)            # not async: fine

            async def handler(loop):
                def work():
                    time.sleep(1)        # runs in an executor: fine
                await loop.run_in_executor(None, work)
                await loop.run_in_executor(None, time.sleep, 1)
            """
        assert lint_snippet(tmp_path, src, [rule_by_id("A001")]) == []

    def test_fcntl_and_path_io(self, tmp_path):
        src = """
            import fcntl
            from pathlib import Path

            async def handler(f):
                fcntl.flock(f, 1)
                Path("x").read_text()
            """
        found = lint_snippet(tmp_path, src, [rule_by_id("A001")])
        assert len(found) == 2


# ---------------------------------------------------------------------------
# D001 determinism


class TestD001:
    HEADER = "__bit_identity_critical__ = True\n"

    def test_wallclock_random_uuid_set_iteration(self, tmp_path):
        src = self.HEADER + textwrap.dedent("""
            import random
            import time
            import uuid

            def key(parts):
                t = time.time()
                r = random.random()
                u = uuid.uuid4()
                for p in {1, 2, 3}:
                    pass
                return t, r, u
            """)
        found = lint_snippet(tmp_path, src, [rule_by_id("D001")])
        assert len(found) == 4

    def test_non_critical_module_ignored(self, tmp_path):
        src = """
            import time

            def anywhere():
                return time.time()
            """
        assert lint_snippet(tmp_path, src, [rule_by_id("D001")]) == []

    def test_sorted_set_passes(self, tmp_path):
        src = self.HEADER + textwrap.dedent("""
            def key(parts):
                for p in sorted({1, 2, 3}):
                    pass
            """)
        assert lint_snippet(tmp_path, src, [rule_by_id("D001")]) == []

    def test_seeded_rng_passes(self, tmp_path):
        src = self.HEADER + textwrap.dedent("""
            import random

            def key(seed):
                rng = random.Random(seed)
                return rng.random()
            """)
        # random.Random(seed) IS flagged (random.* prefix) but the seeded
        # instance's method calls are not — declare-and-suppress is the
        # documented idiom for the constructor line.
        found = lint_snippet(tmp_path, src, [rule_by_id("D001")])
        assert len(found) == 1 and "random.Random" in found[0].message


# ---------------------------------------------------------------------------
# K001 knob discipline


class TestK001:
    def test_raw_reads_flagged(self, tmp_path):
        src = """
            import os
            from os import getenv

            KNOB = "CDT_VIA_CONST"

            def f():
                a = os.environ.get("CDT_DIRECT")
                b = os.getenv("CDT_GETENV", "1")
                c = getenv("CDT_FROMIMPORT")
                d = os.environ["CDT_SUBSCRIPT"]
                e = os.environ.get(KNOB)
                return a, b, c, d, e
            """
        found = lint_snippet(tmp_path, src, [rule_by_id("K001")])
        names = sorted(f.message.split()[4] for f in found)
        assert len(found) == 5
        assert "CDT_VIA_CONST" in " ".join(f.message for f in found)

    def test_non_cdt_reads_pass(self, tmp_path):
        src = """
            import os

            def f():
                return os.environ.get("JAX_PLATFORMS"), os.getenv("HOME")
            """
        assert lint_snippet(tmp_path, src, [rule_by_id("K001")]) == []

    def test_legacy_env_helpers_flagged(self, tmp_path):
        src = """
            from comfyui_distributed_tpu.utils.constants import env_int

            def f():
                return env_int("CDT_LEGACY", 3)
            """
        found = lint_snippet(tmp_path, src, [rule_by_id("K001")])
        assert len(found) == 1 and "legacy" in found[0].message


# ---------------------------------------------------------------------------
# J001 traced purity


class TestJ001:
    def test_impure_traced_functions_flagged(self, tmp_path):
        src = """
            import os
            import time

            import jax
            from jax import shard_map

            @jax.jit
            def decorated(x):
                print("tracing", x)
                return x

            def called(x):
                flag = os.environ.get("CDT_SOMETHING")
                return x if flag else -x

            jitted = jax.jit(called)

            def sharded(x):
                t = time.time()
                return x * t

            f = shard_map(sharded, mesh=None)
            """
        found = lint_snippet(tmp_path, src, [rule_by_id("J001")])
        kinds = " | ".join(f.message for f in found)
        assert len(found) == 3
        assert "print" in kinds and "os.environ.get" in kinds \
            and "time.time" in kinds

    def test_pure_traced_function_passes(self, tmp_path):
        src = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(x, w):
                return jnp.dot(x, w)

            g = jax.jit(lambda x: x * 2)
            """
        assert lint_snippet(tmp_path, src, [rule_by_id("J001")]) == []

    def test_telemetry_call_in_trace_flagged(self, tmp_path):
        src = """
            import jax
            from comfyui_distributed_tpu.telemetry import metrics as tm

            @jax.jit
            def step(x):
                tm.STEP_SECONDS.observe(1.0)
                return x
            """
        found = lint_snippet(tmp_path, src, [rule_by_id("J001")])
        assert len(found) == 1 and "telemetry" in found[0].message


# ---------------------------------------------------------------------------
# baseline semantics


class TestBaseline:
    def _findings(self, tmp_path):
        return lint_snippet(tmp_path, TestL001.BAD, [rule_by_id("L001")])

    def test_new_stale_unjustified(self, tmp_path):
        found = self._findings(tmp_path)
        gate = apply_baseline(found, {})
        assert [f.site for f in gate.new] == [f.site for f in found]

        baseline = {found[0].site: "known single-writer path"}
        gate = apply_baseline(found, baseline)
        assert len(gate.new) == 1 and gate.new[0].site == found[1].site
        assert gate.stale == [] and not gate.ok

        baseline = {found[0].site: "ok", found[1].site: "ok",
                    "L001:gone.py:X.y:z": "stale entry"}
        gate = apply_baseline(found, baseline)
        assert gate.new == [] and gate.stale == ["L001:gone.py:X.y:z"]
        assert not gate.ok          # the baseline only shrinks

        baseline = {found[0].site: "ok", found[1].site: "TODO: justify"}
        gate = apply_baseline(found, baseline)
        assert gate.unjustified == [found[1].site] and not gate.ok

        baseline = {found[0].site: "ok", found[1].site: "also fine"}
        assert apply_baseline(found, baseline).ok

    def test_write_and_load_roundtrip(self, tmp_path):
        found = self._findings(tmp_path)
        p = tmp_path / "baseline.json"
        write_baseline(found, p, justifications={found[0].site: "reason"})
        loaded = load_baseline(p)
        assert loaded[found[0].site] == "reason"
        assert loaded[found[1].site].startswith("TODO")

    def test_scoped_run_neither_fails_stale_nor_drops_grandfathers(
            self, tmp_path):
        """A single-file or single-rule run must not report the rest of
        the baseline stale, and a scoped --write-baseline must preserve
        out-of-scope entries."""
        from comfyui_distributed_tpu.lint.__main__ import main

        # scoped path: one clean file, repo baseline has 5 A001/K001
        # entries elsewhere — must exit 0, not STALE
        assert main([str(PKG_ROOT / "cluster" / "residency.py")]) == 0
        # scoped rule: no L001 sites are baselined — must exit 0
        assert main(["--rules", "L001"]) == 0

        f = tmp_path / "snippet.py"
        f.write_text(textwrap.dedent(TestL001.BAD), encoding="utf-8")
        findings = run_lint([f], [rule_by_id("L001")], tmp_path)
        bl = tmp_path / "bl.json"
        write_baseline(findings, bl,
                       justifications={x.site: "ok" for x in findings},
                       preserve={"K001:other/file.py:<module>:CDT_X":
                                 "someone else's grandfather"})
        loaded = load_baseline(bl)
        assert "K001:other/file.py:<module>:CDT_X" in loaded
        assert len(loaded) == len(findings) + 1

    def test_site_ids_are_line_number_free(self, tmp_path):
        a = self._findings(tmp_path)
        shifted = "\n\n\n" + textwrap.dedent(TestL001.BAD)
        f = tmp_path / "snippet.py"
        f.write_text(shifted, encoding="utf-8")
        b = run_lint([f], [rule_by_id("L001")], tmp_path)
        assert [x.site for x in a] == [y.site for y in b]


# ---------------------------------------------------------------------------
# the tier-1 gate: the real package


class TestRepoGate:
    @pytest.fixture(scope="class")
    def repo_gate(self):
        findings = run_lint([PKG_ROOT], ALL_RULES, REPO_ROOT)
        return apply_baseline(findings, load_baseline())

    def test_package_lints_clean_against_baseline(self, repo_gate):
        msgs = [f.render() for f in repo_gate.new]
        assert repo_gate.new == [], f"non-baselined findings: {msgs}"
        assert repo_gate.stale == [], (
            f"stale baseline entries (remove them — the baseline only "
            f"shrinks): {repo_gate.stale}")

    def test_every_baseline_entry_is_justified(self, repo_gate):
        assert repo_gate.unjustified == []
        for site, just in load_baseline().items():
            assert just.strip() and not just.strip().startswith("TODO"), site

    def test_knob_docs_regeneration_clean(self):
        from comfyui_distributed_tpu.lint.knobdocs import render_markdown

        committed = (REPO_ROOT / "docs" / "knobs.md").read_text(
            encoding="utf-8")
        assert committed == render_markdown(), (
            "docs/knobs.md is stale — run `python -m "
            "comfyui_distributed_tpu.lint --write-knob-docs`")

    def test_seeded_regressions_are_caught(self, tmp_path):
        """Acceptance (ISSUE 12): an injected unlocked mutation, raw env
        read, and blocking-call-in-async must each be caught — proves the
        tier-1 lint test can't silently become a yes-machine."""
        seeded = """
            import os
            import threading
            import time

            class SeededRegistry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._data = {}

                def ok(self, k):
                    with self._lock:
                        self._data[k] = 1

                def racy(self, k):
                    self._data[k] = 2

            def read_knob():
                return os.environ.get("CDT_SEEDED_KNOB")

            async def handler():
                time.sleep(1)
            """
        found = lint_snippet(tmp_path, seeded)
        rules = {f.rule for f in found}
        assert {"L001", "A001", "K001"} <= rules, found


# ---------------------------------------------------------------------------
# knob registry


class TestKnobRegistry:
    def test_parse_once_per_value(self, monkeypatch):
        monkeypatch.setenv("CDT_FD_MAX_WAIT_MS", "40")
        assert constants.FD_MAX_WAIT_MS.get() == 40.0
        monkeypatch.setenv("CDT_FD_MAX_WAIT_MS", "55")
        assert constants.FD_MAX_WAIT_MS.get() == 55.0
        monkeypatch.delenv("CDT_FD_MAX_WAIT_MS")
        assert constants.FD_MAX_WAIT_MS.get() is None

    def test_garbage_raises_descriptively(self, monkeypatch):
        monkeypatch.setenv("CDT_FD_MAX_WAIT_MS", "soon")
        with pytest.raises(constants.KnobError, match="CDT_FD_MAX_WAIT_MS"):
            constants.FD_MAX_WAIT_MS.get()
        monkeypatch.setenv("CDT_WARMUP", "maybe")
        with pytest.raises(constants.KnobError, match="not a boolean"):
            constants.WARMUP.get()
        monkeypatch.setenv("CDT_OFFLOAD_LADDER", "bogus")
        with pytest.raises(constants.KnobError, match="CDT_OFFLOAD_LADDER"):
            constants.OFFLOAD_LADDER.get()

    def test_fallback_knobs_warn_and_default(self, monkeypatch):
        monkeypatch.setenv("CDT_RING_BLOCK", "banana")
        assert constants.RING_BLOCK.get() == 1024

    def test_optbool_tristate(self, monkeypatch):
        monkeypatch.delenv("CDT_OFFLOAD", raising=False)
        assert constants.OFFLOAD.get() is None
        monkeypatch.setenv("CDT_OFFLOAD", "1")
        assert constants.OFFLOAD.get() is True
        monkeypatch.setenv("CDT_OFFLOAD", "off")
        assert constants.OFFLOAD.get() is False

    def test_keep_empty_distinguishes_unset(self, monkeypatch):
        monkeypatch.delenv("CDT_CACHE_DIR", raising=False)
        assert constants.CACHE_DIR.get() is None
        monkeypatch.setenv("CDT_CACHE_DIR", "")
        assert constants.CACHE_DIR.get() == ""

    def test_empty_telemetry_means_off(self, monkeypatch):
        """`CDT_TELEMETRY=` (empty, the shell disable idiom) must read
        False — the pre-registry behavior."""
        monkeypatch.setenv("CDT_TELEMETRY", "")
        assert constants.TELEMETRY.get() is False
        monkeypatch.delenv("CDT_TELEMETRY")
        assert constants.TELEMETRY.get() is True

    def test_lookup_and_unknown_knob(self):
        assert constants.knob("CDT_LORA_DIR") is constants.LORA_DIR
        with pytest.raises(constants.KnobError, match="not a declared"):
            constants.knob("CDT_NOT_A_KNOB")

    def test_duplicate_declaration_rejected(self):
        with pytest.raises(constants.KnobError, match="duplicate"):
            constants.knob_int("CDT_WORKER_INDEX", 0, "workers", "dup")

    def test_every_knob_has_subsystem_and_help(self):
        for k in constants.KNOBS.all():
            assert k.subsystem and k.help, k.name


# ---------------------------------------------------------------------------
# lock-order detector


@pytest.fixture
def lock_tracking():
    lockorder.reset()
    lockorder.force_enabled(True)
    yield
    lockorder.force_enabled(None)
    lockorder.reset()


class TestLockOrder:
    def test_two_thread_inversion_detected(self, lock_tracking):
        """A REAL inversion: thread 1 takes A->B, thread 2 takes B->A.
        The second ordering must raise at acquisition time."""
        a = lockorder.tracked_lock("inv.A")
        b = lockorder.tracked_lock("inv.B")
        with a:
            with b:
                pass
        caught = []

        def second():
            try:
                with b:
                    with a:
                        pass
            except lockorder.LockOrderError as e:
                caught.append(e)

        t = threading.Thread(target=second)
        t.start()
        t.join(timeout=10)
        assert caught, "B->A after A->B must raise LockOrderError"
        assert "inv.A" in str(caught[0]) and "inv.B" in str(caught[0])
        assert len(lockorder.snapshot()["inversions"]) == 1
        with pytest.raises(lockorder.LockOrderError):
            lockorder.assert_clean()

    def test_consistent_order_is_clean(self, lock_tracking):
        a = lockorder.tracked_lock("ord.A")
        b = lockorder.tracked_lock("ord.B")

        def worker():
            for _ in range(50):
                with a:
                    with b:
                        pass

        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert lockorder.snapshot()["inversions"] == []
        assert ("ord.A", "ord.B") in [tuple(e) for e in
                                      lockorder.snapshot()["edges"]]
        lockorder.assert_clean()

    def test_reentrant_and_same_name_no_edge(self, lock_tracking):
        r = lockorder.tracked_lock("reent", reentrant=True)
        with r:
            with r:
                pass
        assert lockorder.snapshot()["edges"] == []

    def test_disabled_records_nothing(self):
        lockorder.reset()
        lockorder.force_enabled(False)
        try:
            a = lockorder.tracked_lock("off.A")
            b = lockorder.tracked_lock("off.B")
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass
            assert lockorder.snapshot() == {"edges": [], "inversions": []}
        finally:
            lockorder.force_enabled(None)

    def test_release_order_bookkeeping(self, lock_tracking):
        a = lockorder.tracked_lock("rel.A")
        b = lockorder.tracked_lock("rel.B")
        a.acquire()
        b.acquire()
        a.release()            # non-LIFO release must not corrupt holds
        b.release()
        with b:
            pass               # no stale "a held" edge may appear
        assert ("rel.A", "rel.B") in [tuple(e) for e in
                                      lockorder.snapshot()["edges"]]
        assert len(lockorder.snapshot()["edges"]) == 1


@pytest.mark.chaos
class TestLockOrderChaos:
    def test_lock_order_registries_under_concurrency(self, lock_tracking):
        """Chaos stage 0 leg: hammer the real shared registries (BREAKERS,
        DRAIN, a CacheTier, telemetry) from racing threads and assert the
        recorded acquisition graph holds ZERO inversions — every chaos
        event doubles as a race-detector run."""
        import numpy as np

        from comfyui_distributed_tpu.cluster.cache.store import CacheTier
        from comfyui_distributed_tpu.cluster.elastic.states import DRAIN
        from comfyui_distributed_tpu.cluster.resilience import BREAKERS
        from comfyui_distributed_tpu.telemetry import metrics as _tm

        tier = CacheTier("chaoslock", max_bytes=1 << 20)
        arr = {"x": np.zeros((8,), dtype=np.float32)}
        errors = []

        def storm(i):
            try:
                for n in range(30):
                    wid = f"w{(i + n) % 3}"
                    BREAKERS.get(wid).record_failure()
                    BREAKERS.get(wid).record_success()
                    BREAKERS.states()
                    DRAIN.mark_draining(wid)
                    DRAIN.reactivate(wid)
                    tier.put(f"k{n % 7}", arr)
                    tier.get(f"k{(n + 1) % 7}")
                    _tm.CACHE_HITS.labels(tier="chaoslock").inc()
            except Exception as e:          # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=storm, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert errors == [], errors
        snap = lockorder.snapshot()
        assert snap["inversions"] == [], snap
        assert snap["edges"], "detector armed but recorded no edges"


# ---------------------------------------------------------------------------
# cdtlint v2 flow rules (ISSUE 20): call graph + taint + wire contract


def lint_files(tmp_path, files, rules=None):
    """Multi-file variant of lint_snippet for cross-module flow tests.
    Non-.py entries (e.g. a fixture docs/api.md) are written but not
    linted — W001 reads them from the repo root."""
    paths = []
    for rel, src in files.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(src), encoding="utf-8")
        if f.suffix == ".py":
            paths.append(f)
    return run_lint(paths, rules or ALL_RULES, tmp_path)


class TestA002:
    def test_transitive_blocking_chain_named(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import time

            def leaf():
                time.sleep(0.5)

            def outer():
                leaf()

            async def handler():
                outer()
            """)
        a002 = [f for f in found if f.rule == "A002"]
        assert len(a002) == 1, found
        msg = a002[0].render()
        # the finding must name the full hop chain, not just the leaf
        assert "outer" in msg and "leaf" in msg and "time.sleep" in msg

    def test_cross_module_chain(self, tmp_path):
        found = lint_files(tmp_path, {
            "helpers.py": """
                import subprocess

                def run_tool():
                    subprocess.run(["true"])
                """,
            "routes.py": """
                import helpers

                async def handler(request):
                    helpers.run_tool()
                """,
        })
        a002 = [f for f in found if f.rule == "A002"]
        assert len(a002) == 1 and a002[0].path == "routes.py", found
        assert "run_tool" in a002[0].render()

    def test_heavy_codec_chain_flagged(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import base64

            def encode(buf):
                return base64.b64encode(buf)

            async def handler(buf):
                return encode(buf)
            """)
        assert any(f.rule == "A002" and "b64" in f.render().lower()
                   for f in found), found

    def test_executor_offload_sanitizes_the_chain(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import asyncio
            import functools
            import time

            def leaf():
                time.sleep(0.5)

            async def fine(loop):
                await loop.run_in_executor(None, leaf)

            async def fine_partial(loop):
                await loop.run_in_executor(None, functools.partial(leaf))

            async def fine_to_thread():
                await asyncio.to_thread(leaf)
            """)
        assert [f for f in found if f.rule in ("A001", "A002")] == [], found

    def test_blocking_scheduled_onto_loop_flagged(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import time

            def leaf():
                time.sleep(0.5)

            def sync_caller(loop):
                loop.call_soon(leaf)
            """)
        a002 = [f for f in found if f.rule == "A002"]
        assert len(a002) == 1 and "leaf" in a002[0].render(), found

    def test_source_line_suppression_kills_whole_class(self, tmp_path):
        """`# cdtlint: disable=A002` on the LEAF call's line exempts every
        transitive caller — one justified comment at the root instead of a
        baseline entry per call site (the load_config precedent)."""
        found = lint_snippet(tmp_path, """
            import time

            def leaf():
                time.sleep(0.01)  # cdtlint: disable=A002

            def outer():
                leaf()

            async def h1():
                outer()

            async def h2():
                outer()
            """)
        assert [f for f in found if f.rule == "A002"] == [], found


class TestExecutorWrapperExemption:
    """Satellite (ISSUE 20): A001's executor exemption unwraps partial /
    lambda wrappers — and keeps the eager-evaluation true positive."""

    def test_partial_and_lambda_args_exempt(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import functools
            import time

            async def ok_partial(loop):
                await loop.run_in_executor(
                    None, functools.partial(time.sleep, 1))

            async def ok_lambda(loop, path):
                await loop.run_in_executor(
                    None, lambda: open(path).read())

            async def ok_local_alias(loop, path):
                run = lambda: open(path).read()
                await loop.run_in_executor(None, run)
            """)
        assert [f for f in found if f.rule in ("A001", "A002")] == [], found

    def test_eager_call_inside_partial_still_flagged(self, tmp_path):
        # partial(open(path).read) EVALUATES open() on the loop before
        # the executor ever runs — the exemption must not swallow it
        found = lint_snippet(tmp_path, """
            import functools

            async def still_bad(loop, path):
                await loop.run_in_executor(
                    None, functools.partial(open(path).read))
            """)
        assert any(f.rule == "A001" for f in found), found

    def test_unwrapped_direct_call_still_flagged(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import time

            async def bad():
                time.sleep(1)
            """)
        assert any(f.rule == "A001" for f in found), found


class TestL002:
    def test_lock_held_across_await(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import asyncio
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()

                async def bad(self):
                    with self._lock:
                        await asyncio.sleep(0)
            """)
        l002 = [f for f in found if f.rule == "L002"]
        assert len(l002) == 1 and "_lock" in l002[0].render(), found

    def test_lock_held_across_transitive_blocking(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import threading
            import time

            _lock = threading.Lock()

            def slow():
                time.sleep(0.5)

            async def bad():
                with _lock:
                    slow()
            """)
        l002 = [f for f in found if f.rule == "L002"]
        assert len(l002) == 1, found
        assert "slow" in l002[0].render()

    def test_async_with_and_release_before_await_clean(self, tmp_path):
        found = lint_snippet(tmp_path, """
            import asyncio
            import threading

            _lock = threading.Lock()

            async def good_async_with():
                async with asyncio.Lock():
                    await asyncio.sleep(0)

            async def good_release_first():
                with _lock:
                    x = 1
                await asyncio.sleep(0)
                return x
            """)
        assert [f for f in found if f.rule == "L002"] == [], found


class TestD002:
    def test_cross_module_laundering_into_sink(self, tmp_path):
        found = lint_files(tmp_path, {
            "helpers.py": """
                import time

                def now_key():
                    return f"k-{time.time()}"
                """,
            "sink.py": """
                __bit_identity_critical__ = True

                import helpers

                def cache_key():
                    return helpers.now_key()
                """,
        })
        d002 = [f for f in found if f.rule == "D002"]
        assert len(d002) == 1 and d002[0].path == "sink.py", found
        msg = d002[0].render()
        assert "now_key" in msg and "time.time" in msg

    def test_knob_read_sanitizes_env_taint(self, tmp_path):
        found = lint_files(tmp_path, {
            "helpers.py": """
                from comfyui_distributed_tpu.utils.constants import knob_int

                KNOB = knob_int("CDT_X", 1, "test", "help")

                def knob_val():
                    return KNOB.get()
                """,
            "sink.py": """
                __bit_identity_critical__ = True

                import helpers

                def cache_key():
                    return helpers.knob_val()
                """,
        })
        assert [f for f in found if f.rule == "D002"] == [], found

    def test_sorted_kills_set_order_taint(self, tmp_path):
        found = lint_files(tmp_path, {
            "helpers.py": """
                def ordered_ids(items):
                    return sorted(set(items))

                def unordered_ids(items):
                    return list(set(items))
                """,
            "sink.py": """
                __bit_identity_critical__ = True

                import helpers

                def good(items):
                    return helpers.ordered_ids(items)

                def bad(items):
                    return helpers.unordered_ids(items)
                """,
        })
        d002 = [f for f in found if f.rule == "D002"]
        assert len(d002) == 1 and "unordered_ids" in d002[0].render(), found

    def test_non_sink_module_ignored(self, tmp_path):
        found = lint_files(tmp_path, {
            "helpers.py": """
                import time

                def now_key():
                    return time.time()
                """,
            "plain.py": """
                import helpers

                def whatever():
                    return helpers.now_key()
                """,
        })
        assert [f for f in found if f.rule == "D002"] == [], found


class TestW001:
    APP = "comfyui_distributed_tpu/api/app.py"

    def _files(self, doc_rows):
        return {
            self.APP: """
                from aiohttp import web

                from .schemas import require_fields

                async def ok(request):
                    return web.json_response({})

                async def raw(request):
                    body = await request.json()
                    return web.json_response(body)

                async def checked(request):
                    body = await request.json()
                    require_fields(body, "x")
                    return web.json_response(body)

                def create_app(router):
                    router.add_get("/distributed/ok", ok)
                    router.add_post("/distributed/undocumented", ok)
                    router.add_post("/distributed/raw", raw)
                    router.add_post("/distributed/checked", checked)
                """,
            "docs/api.md": "\n".join(
                f"| {row} | stuff |" for row in doc_rows) + "\n",
        }

    def test_contract_violations(self, tmp_path):
        found = lint_files(tmp_path, self._files(
            ["/distributed/ok", "/distributed/raw",
             "/distributed/checked", "/distributed/ghost"]))
        w = sorted(f.render() for f in found if f.rule == "W001")
        assert len(w) == 3, w
        assert any("undocumented" in m and "not documented" in m for m in w)
        assert any("raw" in m and "validat" in m for m in w)
        assert any("ghost" in m and "no route registers" in m for m in w)

    def test_in_sync_app_is_clean(self, tmp_path):
        found = lint_files(tmp_path, self._files(
            ["/distributed/ok", "/distributed/undocumented",
             "/distributed/raw", "/distributed/checked"]))
        w = [f for f in found if f.rule == "W001"]
        # only the unvalidated-body finding remains
        assert len(w) == 1 and "raw" in w[0].render(), w

    def test_without_app_module_rule_is_gated_off(self, tmp_path):
        found = lint_snippet(tmp_path, """
            def create_app(router, h):
                router.add_get("/distributed/whatever", h)
            """)
        assert [f for f in found if f.rule == "W001"] == [], found


class TestFlowSeededRegressions:
    def test_repo_gate_style_seeds_are_caught(self, tmp_path):
        """ISSUE 20 acceptance: one real violation per flow rule, planted
        in scratch modules, must each be caught (mirrors the ISSUE 12
        seeded-violation pattern so the v2 gate can't rot silently)."""
        found = lint_files(tmp_path, {
            "seed_helpers.py": """
                import threading
                import time

                _lock = threading.Lock()

                def wall_key():
                    return time.time()

                def chain_leaf():
                    time.sleep(0.1)

                def chain_mid():
                    chain_leaf()
                """,
            "seed_async.py": """
                import asyncio

                import seed_helpers

                async def a002_seed():
                    seed_helpers.chain_mid()

                async def l002_seed():
                    with seed_helpers._lock:
                        await asyncio.sleep(0)
                """,
            "seed_sink.py": """
                __bit_identity_critical__ = True

                import seed_helpers

                def d002_seed():
                    return seed_helpers.wall_key()
                """,
        })
        rules = {f.rule for f in found}
        assert {"A002", "L002", "D002"} <= rules, sorted(
            f.render() for f in found)


# ---------------------------------------------------------------------------
# runtime event-loop stall sanitizer (lint/loopstall.py)


@pytest.fixture
def stall_tracking():
    from comfyui_distributed_tpu.lint import loopstall

    loopstall.reset()
    loopstall.force_enabled(True)
    yield loopstall
    loopstall.force_enabled(None)
    loopstall.reset()


class TestLoopStall:
    def test_seeded_stall_names_the_frame(self, stall_tracking):
        """ISSUE 20 acceptance: a deliberate 200 ms loop block must be
        recorded with the offending callback NAMED (default threshold
        CDT_LOOP_STALL_MS=100)."""
        import asyncio
        import time

        loopstall = stall_tracking

        def seeded_block():
            time.sleep(0.2)

        async def main():
            asyncio.get_running_loop().call_soon(seeded_block)
            await asyncio.sleep(0.45)

        asyncio.run(main())
        stalls = loopstall.snapshot()["stalls"]
        assert len(stalls) == 1, stalls
        s = stalls[0]
        assert "seeded_block" in s["callback"]
        assert s["duration_ms"] >= 150
        if s["observed"] == "sampled":
            # the sampler caught it live: the stack must name the frame
            assert "seeded_block" in s["stack"]
        with pytest.raises(loopstall.LoopStallError) as exc:
            loopstall.assert_clean()
        assert "seeded_block" in str(exc.value)

    def test_fast_callbacks_record_nothing(self, stall_tracking):
        import asyncio

        loopstall = stall_tracking

        async def main():
            for _ in range(20):
                await asyncio.sleep(0)

        asyncio.run(main())
        assert loopstall.snapshot()["stalls"] == []
        loopstall.assert_clean()

    def test_disabled_records_nothing(self):
        import asyncio
        import time

        from comfyui_distributed_tpu.lint import loopstall

        loopstall.reset()
        loopstall.force_enabled(False)
        try:
            async def main():
                asyncio.get_running_loop().call_soon(
                    lambda: time.sleep(0.15))
                await asyncio.sleep(0.25)

            asyncio.run(main())
            assert loopstall.snapshot()["stalls"] == []
        finally:
            loopstall.force_enabled(None)
            loopstall.reset()
