"""The set-up ledger (``telemetry/build.py``): every build second under one
program and one phase, SELF seconds where building nests, the cache's
nameless events joined to the program they belong to, and nothing at all
with telemetry off."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from comfyui_distributed_tpu import telemetry
from comfyui_distributed_tpu.telemetry import build
from comfyui_distributed_tpu.telemetry import metrics as tm
from comfyui_distributed_tpu.utils import compile_cache as cc

BACKEND = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture
def ledger():
    """Telemetry on and zeroed, the listeners registered, and the suite's
    persistent cache taking every program, however quick; all put back.
    (The cache keeps conftest's directory: JAX opens it once a process, and
    a test that moved it would change what later tests write to.)"""
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = [getattr(jax.config, n) for n in names]
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.REGISTRY.reset()
    telemetry.SPAN_STORE.reset()
    cc._count_compiles()
    for name, value in zip(names, (0.0, 0)):
        jax.config.update(name, value)
    try:
        yield
    finally:
        for name, value in zip(names, saved):
            jax.config.update(name, value)
        telemetry.REGISTRY.reset()
        telemetry.SPAN_STORE.reset()
        telemetry.set_enabled(was)


def _series(metric) -> dict:
    return {tuple(labels.values()): snap for labels, snap in metric.series()}


def _build(program=None, phase=None, field="sum") -> float:
    return sum(snap[field]
               for (p, ph), snap in _series(tm.PROGRAM_BUILD_SECONDS).items()
               if program in (None, p) and (
                   ph in phase if isinstance(phase, tuple)
                   else phase in (None, ph)))


def _cache(program) -> dict:
    return {o: snap["value"]
            for (p, o), snap in _series(tm.PROGRAM_CACHE).items()
            if p == program}


def _tiny_program(salt: float):
    """A new function object each call — a new trace — of one HLO a
    ``salt``: a fresh salt is a program no cache has seen."""
    def ledger_tiny(x):
        return jnp.sin(x) * 2.0 + salt
    return jax.jit(ledger_tiny)


def _salt() -> float:
    return float(int.from_bytes(os.urandom(3), "big"))


X = jnp.arange(8, dtype=jnp.float32)


def test_a_program_is_traced_lowered_compiled_then_read(ledger):
    X.block_until_ready()
    salt = _salt()
    _tiny_program(salt)(X).block_until_ready()
    for phase in ("trace", "lower", "compile"):
        assert _build("ledger_tiny", phase, "count") == 1, phase
    assert _build("ledger_tiny", ("cache_key", "cache_read"), "count") == 0
    assert _cache("ledger_tiny") == {"miss": 1.0}
    compiled = _build("ledger_tiny", "compile")

    _tiny_program(salt)(X).block_until_ready()     # a restart, in small
    assert _build("ledger_tiny", "trace", "count") == 2
    assert _build("ledger_tiny", "lower", "count") == 2
    assert _build("ledger_tiny", "cache_read", "count") == 1
    assert _build("ledger_tiny", "cache_key", "count") == 1
    assert _build("ledger_tiny", "compile") == compiled
    assert _cache("ledger_tiny") == {"miss": 1.0, "hit": 1.0}
    # one listener, one number: the backend phases are the old series
    assert _build(phase=("cache_key", "cache_read", "compile")) == \
        pytest.approx(tm.XLA_COMPILE_SECONDS.series()[0][1]["sum"],
                      rel=1e-9)
    requests = _series(tm.COMPILE_CACHE_REQUESTS)
    assert requests[("hit",)]["value"] >= 1
    assert requests[("miss",)]["value"] >= 1


def test_a_program_the_cache_never_wrote_is_uncached(ledger):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
    _tiny_program(_salt())(X).block_until_ready()
    assert _cache("ledger_tiny") == {"uncached": 1.0}
    assert _build("ledger_tiny", "compile", "count") == 1


@pytest.mark.parametrize("raw, program", [
    ("seg_body", "seg_body"), ("jit_seg_body", "seg_body"),
    ("jit(seg_body)", "seg_body"), ("jit__pad", "_pad"),
    ("pmap(step)", "step"), ("jit", "jit"), ("", "unnamed"),
    ("<lambda>", "<lambda>"),
])
def test_one_name_a_program(raw, program):
    assert build.program_of(raw) == program


def test_two_threads_keep_their_cache_outcomes_apart(ledger):
    """The cache's events carry no name: they wait, PER THREAD, for the
    backend event that does. Two lookups interleaved event by event."""
    turn = threading.Barrier(2, timeout=30)

    def reads():
        build.on_event(LOOKUP)
        turn.wait()
        build.on_event(HIT)
        build.on_duration(RETRIEVAL, 0.25)
        turn.wait()
        turn.wait()
        build.on_duration(BACKEND, 0.3, fun_name="jit(reader)")

    def compiles():
        turn.wait()
        build.on_event(LOOKUP)
        turn.wait()
        build.on_event(MISS)
        build.on_duration(BACKEND, 2.0, fun_name="jit(compiler)")
        turn.wait()

    threads = [threading.Thread(target=f) for f in (reads, compiles)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert _cache("reader") == {"hit": 1.0}
    assert _cache("compiler") == {"miss": 1.0}
    assert _build("reader", "cache_read") == 0.25
    assert _build("reader", "cache_key") == pytest.approx(0.05)
    assert _build("compiler", "compile") == 2.0
    assert _build("reader", "compile", "count") == 0
    assert _build("compiler", "cache_read", "count") == 0
    # a hit left behind by a lookup that never closed names no later one
    build.on_event(LOOKUP)
    build.on_event(HIT)
    build.on_event(LOOKUP)
    build.on_duration(BACKEND, 0.1, fun_name="jit(later)")
    assert _cache("later") == {"uncached": 1.0}


def test_nested_traces_are_self_seconds(ledger):
    """An inner jit is traced inside its caller's trace: both events hold
    the inner seconds, the ledger counts them once."""
    def ledger_inner(x):
        time.sleep(0.2)             # tracing that takes a while
        return jnp.cos(x)

    inner = jax.jit(ledger_inner)

    @jax.jit
    def ledger_outer(x):
        return inner(x) + 1.0

    t0 = time.perf_counter()
    ledger_outer(X).block_until_ready()
    wall = time.perf_counter() - t0
    inner_s = _build("ledger_inner", "trace")
    outer_s = _build("ledger_outer", "trace")
    assert inner_s >= 0.2 and 0 <= outer_s < 0.2
    assert inner_s + outer_s <= wall
    assert _build() <= wall             # every phase of the call
    assert build.since(t0) == pytest.approx(_build(), rel=1e-9)


def test_the_listener_subtracts_only_what_arrived_inside_the_event(ledger):
    build.on_duration(TRACE, 5.0, fun_name="before")     # ended long ago
    time.sleep(0.05)
    t0 = time.perf_counter()
    time.sleep(0.2)
    build.note(0.03)                                     # an inner build
    time.sleep(0.05)
    seconds = time.perf_counter() - t0
    build.on_duration(TRACE, seconds, fun_name="outer")
    assert _build("outer", "trace") == pytest.approx(seconds - 0.03,
                                                     abs=1e-9)
    build.on_duration(TRACE, 0.001, fun_name="clamped")  # less than inside
    assert 0.0 <= _build("clamped", "trace") <= 0.001


def test_old_arrivals_fold_without_moving_a_later_reading(monkeypatch):
    """The per-thread history is bounded: what falls off its end is still
    in the total, and right for every reading taken after it."""
    monkeypatch.setattr(build, "_KEEP", 8)
    seen = {}

    def on_a_thread_of_its_own():       # a history no other test wrote to
        t0 = time.perf_counter()
        for _ in range(5):
            build.note(1.0)
        t1 = time.perf_counter()
        for _ in range(20):
            build.note(0.5)
        t2 = time.perf_counter()
        build.note(0.25)
        seen.update(kept=len(build._mine.at), last=build.since(t2),
                    now=build.since(time.perf_counter()),
                    old=build.since(t1), older=build.since(t0))

    thread = threading.Thread(target=on_a_thread_of_its_own)
    thread.start()
    thread.join(timeout=30)
    assert not thread.is_alive()
    assert seen["kept"] <= 9
    assert seen["last"] == 0.25 and seen["now"] == 0.0
    # older readings see what the history still holds, never more
    assert 0.25 <= seen["old"] <= 10.25
    assert seen["older"] == seen["old"]


def test_a_weights_span_records_self_seconds(ledger):
    X.block_until_ready()
    t0 = time.perf_counter()
    with build.weights_span("init", "tiny-model") as attrs:
        _tiny_program(_salt())(X).block_until_ready()
        time.sleep(0.05)
        attrs(bytes=1234)
    wall = time.perf_counter() - t0
    (labels, snap), = tm.WEIGHTS_SECONDS.series()
    assert labels == {"model": "tiny-model", "phase": "init"}
    assert snap["count"] == 1 and 0.05 <= snap["sum"] <= wall
    assert snap["sum"] + _build() <= wall
    span, = [s for t in telemetry.SPAN_STORE._traces.values() for s in t]
    assert span["name"] == "weights.init"
    assert span["attrs"] == {"model": "tiny-model", "bytes": "1234"}
    # a span shorter than the build reported inside it is 0, never less
    t0 = time.perf_counter()
    build.note(10.0)
    assert build.self_seconds(t0, 0.5) == 0.0


def test_a_bundles_weights_are_drawn_leaf_by_leaf_under_its_name(ledger):
    """No program holds a whole model's initialisation (it was ``<lambda>``,
    ``jax.jit`` of flax's ``init``): the abstract pass is ``init_shapes``,
    a draw is ``draw_leaf``, and the bundle's line counts both."""
    from comfyui_distributed_tpu.models import registry

    bundle = registry._build_bundle("tiny", registry.PRESETS["tiny"], None)
    drawn = sum(len(jax.tree_util.tree_leaves(tree)) for tree in (
        bundle.pipeline.unet_params, bundle.pipeline.vae.enc_params,
        bundle.pipeline.vae.dec_params, bundle.text_encoder.params))
    (labels, leaves), = tm.WEIGHTS_DRAWN_LEAVES.series()
    (also, programs), = tm.WEIGHTS_DRAW_PROGRAMS.series()
    assert labels == also == {"model": "tiny"}
    assert leaves["value"] == drawn > 300
    assert 4 <= programs["value"] < drawn / 3
    built = {program for program, _ in _series(tm.PROGRAM_BUILD_SECONDS)}
    assert "init_shapes" in built and "<lambda>" not in built
    (labels, _), = tm.WEIGHTS_SECONDS.series()
    assert labels == {"model": "tiny", "phase": "init"}


def test_a_pools_builds_are_one_entry_the_wall_its_opener_waited(ledger):
    """Programs built side by side: seconds summed over the pool's threads
    would be counted twice, so they are not counted there at all — the
    cache's outcomes still are, by name."""
    from concurrent.futures import ThreadPoolExecutor

    X.block_until_ready()
    programs = [_tiny_program(_salt()) for _ in range(4)]
    before = _build()
    t0 = time.perf_counter()
    with build.weights_span("init", "pooled-model"):
        with build.pooled_builds("ledger_pool"), \
                ThreadPoolExecutor(4, initializer=build.in_pool) as pool:
            for out in pool.map(lambda program: program(X), programs):
                out.block_until_ready()
    wall = time.perf_counter() - t0
    assert _build("ledger_pool", "compile", "count") == 1
    assert 0 < _build("ledger_pool", "compile") <= wall
    assert _build("ledger_tiny") == 0
    assert _build() - before == pytest.approx(_build("ledger_pool"))
    # compile_s stays cache_read_s + miss_compile_s: the backend seconds too
    assert tm.XLA_COMPILE_SECONDS.series()[0][1]["sum"] == pytest.approx(
        _build(phase=("cache_key", "cache_read", "compile")), rel=1e-9)
    assert _cache("ledger_tiny") == {"miss": 4.0}
    # ... and the span around the pool is net of it, as of any build
    (_, snap), = tm.WEIGHTS_SECONDS.series()
    assert snap["sum"] + _build("ledger_pool") <= wall
    # the opener's own thread was never the pool's
    _tiny_program(_salt())(X).block_until_ready()
    assert _build("ledger_tiny", "compile", "count") == 1


def test_bind_weights_first_run_is_the_first_call_net_of_its_build(ledger):
    from comfyui_distributed_tpu.diffusion.pipeline import bind_weights

    def ledger_bound(w, x):
        return jnp.tanh(x) * w

    fn = bind_weights(jax.jit(ledger_bound), jnp.float32(3.0),
                      label="ledger_bound_label")
    before = _build()               # the weight's own little programs
    fn(X)
    (_, whole), = tm.PIPELINE_COMPILE_SECONDS.series()
    assert whole["count"] == 1
    first_run = _build("ledger_bound_label", "first_run")
    built = _build() - first_run - before
    # compiled, or read where an earlier run of the suite left it
    assert _build("ledger_bound", ("compile", "cache_read"), "count") == 1
    assert built > 0
    assert first_run + built == pytest.approx(whole["sum"], rel=1e-9)
    fn(X)                                   # a steady call: nothing new
    assert _build("ledger_bound_label", "first_run", "count") == 1
    assert tm.PIPELINE_COMPILE_SECONDS.series()[0][1]["count"] == 1
    assert tm.PIPELINE_EXECUTE_SECONDS.series()[0][1]["count"] == 1
    assert _build() == pytest.approx(before + first_run + built)


def test_boot_phases_share_one_pinned_trace(ledger):
    t0 = time.perf_counter()
    time.sleep(0.02)
    build.boot_elapsed("import", t0)
    with build.boot_phase("backend"):
        build.note(0.5)             # a build inside the phase is not boot
        time.sleep(0.02)
    with build.boot_phase("import"):
        time.sleep(0.02)
    gauges = {k[0]: v["value"] for k, v in _series(tm.BOOT_SECONDS).items()}
    assert set(gauges) == {"import", "backend"}
    assert gauges["import"] >= 0.04 and gauges["backend"] == 0.0
    tree = telemetry.SPAN_STORE.tree(build.BOOT_TRACE)
    assert [s["name"] for s in tree] == ["boot.import", "boot.backend",
                                         "boot.import"]
    # the boot trace outlives the ring's oldest-first eviction
    store = telemetry.SPAN_STORE
    for i in range(store.max_traces + 5):
        with telemetry.span("later", trace_id=f"t{i}"):
            pass
    assert store.resolve(build.BOOT_TRACE) == build.BOOT_TRACE
    assert store.resolve("t0") is None
    assert len(store._traces) == store.max_traces


def test_with_telemetry_off_nothing_listens_and_nothing_is_recorded(
        ledger, monkeypatch):
    from conftest import TEST_XLA_CACHE
    from jax._src import monitoring

    from comfyui_distributed_tpu.diffusion.pipeline import bind_weights

    telemetry.set_enabled(False)
    monkeypatch.setattr(cc, "_listening", False)
    before = (len(monitoring.get_event_listeners()),
              len(monitoring.get_event_duration_listeners()))
    cc.enable_compile_cache(TEST_XLA_CACHE, min_compile_secs=0.0)
    assert (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_duration_listeners())) == before
    assert cc._listening is False

    def ledger_off(w, x):
        return x - w

    fn = bind_weights(jax.jit(ledger_off), jnp.float32(1.0),
                      label="ledger_off_label")
    with build.weights_span("init", "off") as attrs, \
            build.boot_phase("backend"):
        attrs(bytes=1)
        fn(X)
    build.boot_elapsed("import", time.perf_counter())
    # the process's listeners (conftest registered them) still hear JAX,
    # and every record they make is refused at the metric
    for metric in (tm.PROGRAM_BUILD_SECONDS, tm.WEIGHTS_SECONDS,
                   tm.PIPELINE_COMPILE_SECONDS):
        assert all(snap["count"] == 0 for _, snap in metric.series())
    assert all(snap["value"] == 0 for m in (tm.PROGRAM_CACHE, tm.BOOT_SECONDS)
               for _, snap in m.series())
    assert not telemetry.SPAN_STORE._traces


def test_a_family_may_raise_its_own_series_cap():
    reg = telemetry.MetricRegistry()
    wide = reg.counter("t_wide_total", "", ("program",), max_series=300)
    narrow = reg.counter("t_narrow_total", "", ("program",))
    for i in range(300):
        wide.labels(program=f"p{i}").inc()
        narrow.labels(program=f"p{i}").inc()
    assert len(wide.series()) == 300 and wide._dropped == 0
    assert len(narrow.series()) == 257 and narrow._dropped == 44
