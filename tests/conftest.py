"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

The TPU analogue of the reference's stub-package pattern (SURVEY §4): the
reference tests "multi-node" behavior against in-process asyncio queues; we
test multi-chip sharding against XLA's virtual CPU devices
(``--xla_force_host_platform_device_count=8``), so every sharded code path
compiles and executes without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compile cache: XLA:CPU compiles of the model stacks dominate the
# suite's wall-clock. Caching compiled executables across runs turns the
# re-run cost into pure execution time. Same function the server and
# bench.py go through (utils/compile_cache.py); the suite keeps a directory
# of its own beside theirs so CPU test artifacts never mix with the chip's.
# JAX_COMPILATION_CACHE_DIR, where set (CI), wins as it does everywhere.
from comfyui_distributed_tpu.utils.compile_cache import (  # noqa: E402
    cache_dir_default, enable_compile_cache)

TEST_XLA_CACHE = cache_dir_default() + "_tests"
enable_compile_cache(TEST_XLA_CACHE, min_compile_secs=0.5)

import faulthandler  # noqa: E402

import pytest  # noqa: E402

# Deadlock evidence (ISSUE 12): a lock inversion used to present as an
# opaque 870 s hang the outer `timeout -k` kills without a trace. Arm
# faulthandler so SIGABRT et al. dump all thread stacks, and give every
# test a watchdog that dumps stacks (repeating, without killing) once it
# runs past CDT_TEST_WATCHDOG_S — the hang still gets killed by the outer
# timeout, but now the log shows WHERE every thread was stuck.
faulthandler.enable()


@pytest.fixture(autouse=True)
def _stack_dump_watchdog():
    from comfyui_distributed_tpu.utils.constants import TEST_WATCHDOG_S

    secs = TEST_WATCHDOG_S.get()
    if secs and secs > 0:
        faulthandler.dump_traceback_later(secs, repeat=True)
        yield
        faulthandler.cancel_dump_traceback_later()
    else:
        yield


@pytest.fixture
def tmp_config(tmp_path, monkeypatch):
    """Point the config system at a throwaway file."""
    from comfyui_distributed_tpu.utils import config as config_mod

    path = tmp_path / "tpu_cluster_config.json"
    monkeypatch.setenv(config_mod.CONFIG_ENV, str(path))
    config_mod.invalidate_cache()
    yield path
    config_mod.invalidate_cache()


@pytest.fixture(autouse=True)
def _reset_resilience_state():
    """Circuit breakers and the fault plan are process-global by design
    (cluster/resilience.py, cluster/faults.py); without a reset, failures
    a test injects against 'w0' would quarantine 'w0' for every later
    test in the session."""
    from comfyui_distributed_tpu.cluster import faults, resilience
    from comfyui_distributed_tpu.cluster.elastic import states as _el_states
    from comfyui_distributed_tpu.lint import lockorder as _lockorder
    from comfyui_distributed_tpu.lint import loopstall as _loopstall

    resilience.BREAKERS.reset()
    _el_states.DRAIN.reset()
    _lockorder.reset()
    # arm the loop-stall sanitizer for the whole suite when the env asks
    # (the chaos suite exports CDT_LOOP_STALL=1); always drop recorded
    # stalls between tests so one slow callback can't fail its neighbors
    _loopstall.reset()
    faults.deactivate()
    yield
    resilience.BREAKERS.reset()
    _el_states.DRAIN.reset()
    faults.deactivate()


@pytest.fixture(autouse=True)
def _isolate_content_cache(tmp_path_factory, monkeypatch):
    """The content cache (cluster/cache) persists next to the XLA cache
    by default; point every test at a throwaway directory so no test
    serves another's entries (or a real leftover). The in-memory tiers
    are per-Controller, so no global reset is needed."""
    monkeypatch.setenv(
        "CDT_CACHE_DIR", str(tmp_path_factory.mktemp("content_cache")))
    yield


@pytest.fixture
def fault_plan():
    """Activate a seeded FaultPlan for the test; returns an installer:
    ``plan = fault_plan("probe@0:drop;...")``."""
    from comfyui_distributed_tpu.cluster import faults

    def install(spec: str):
        return faults.activate(faults.FaultPlan.parse(spec))

    yield install
    faults.deactivate()
