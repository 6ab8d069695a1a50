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
    sites = set(build._sites)       # the process's: other files' models
    build.reset()
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
        build._sites.update(sites)


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
    span, = [s for t in telemetry.SPAN_STORE._traces.values() for s in t
             if not s["name"].startswith("build.")]    # a slow compile's
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
    def listeners():
        return (len(monitoring.get_event_listeners()),
                len(monitoring.get_event_duration_listeners()),
                len(monitoring.get_event_time_span_listeners()))

    before = listeners()
    cc.enable_compile_cache(TEST_XLA_CACHE, min_compile_secs=0.0)
    assert listeners() == before
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


# --- who asked: owners, call sites, the timeline, what a hit saved (PR 66) ---

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"
PHASES = build.PHASES


def _under(under=None, phase=None) -> float:
    return sum(snap["value"] for (u, ph), snap
               in _series(tm.PROGRAM_BUILD_UNDER_SECONDS).items()
               if under in (None, u) and phase in (None, ph))


def _cold(program) -> float:
    return sum(snap["value"] for (p,), snap
               in _series(tm.PROGRAM_COLD_COMPILE_SECONDS).items()
               if p == program)


def _build_spans(trace_id) -> list:
    return [s for s in telemetry.SPAN_STORE.spans(trace_id)
            if s["name"].startswith("build.")]


def _fed(event, seconds, fun_name):
    """JAX's two events of one build, as it sends them: the duration, then
    at once the span."""
    time.sleep(seconds)     # it took that long: nothing older is inside
    build.on_duration(event, seconds, fun_name=fun_name)
    now = time.time()
    build.on_time_span(event, now - seconds, now, fun_name=fun_name)


@pytest.mark.parametrize("phase", PHASES)
def test_by_owner_the_build_seconds_add_up_to_the_ledger(ledger, phase):
    """Every arrival is claimed once or is nobody's: by phase the new
    family reads what ``cdt_program_build_seconds`` reads, after a tiny
    preset's set-up and a labelled program's first call."""
    from comfyui_distributed_tpu.diffusion.pipeline import bind_weights
    from comfyui_distributed_tpu.models import registry

    registry._build_bundle("tiny", registry.PRESETS["tiny"], None)

    def ledger_owned(w, x):
        return jnp.tanh(x) * w + _salt()

    bind_weights(jax.jit(ledger_owned), jnp.float32(3.0),
                 label="ledger_owned_label")(X)
    assert _under(phase=phase) == pytest.approx(_build(phase=phase),
                                                rel=1e-6, abs=1e-9)
    if phase in ("trace", "compile", "first_run"):
        assert _build(phase=phase) > 0
    # set-up has owners: the bundle's span, its abstract passes, the label
    assert _under("weights.init:tiny") > 0
    assert _under("init_shapes", "trace") > 0
    assert _under("first_run:ledger_owned_label", "trace") > 0
    assert _under(build.NOBODY) < 0.2 * _under()


def test_innermost_owner_wins_three_deep(ledger):
    def ledger_leaf(x):
        time.sleep(0.1)
        return jnp.cos(x)

    leaf = jax.jit(ledger_leaf)

    def ledger_branch(x):
        time.sleep(0.2)
        return leaf(x) + 1.0

    branch = jax.jit(ledger_branch)

    @jax.jit
    def ledger_root(x):
        return branch(x) * 2.0

    X.block_until_ready()
    with build.weights_span("init", "nest"):
        ledger_root(X).block_until_ready()
    leaf_s = _build("ledger_leaf", "trace")
    branch_s = _build("ledger_branch", "trace")
    root_s = _build("ledger_root", "trace")
    assert leaf_s >= 0.1 and branch_s >= 0.2 and root_s < 0.1
    # each trace is under the entry right around it, once, with its SELF
    # seconds (beside it only jnp's own little programs: cos, add, multiply)
    assert _under("ledger_leaf") < 0.05
    assert leaf_s <= _under("ledger_branch", "trace") < leaf_s + 0.05
    assert branch_s <= _under("ledger_root", "trace") < branch_s + 0.05
    # the span took the outermost program's phases and nothing twice
    assert root_s <= _under("weights.init:nest", "trace") < root_s + 0.05
    assert _under("weights.init:nest") >= sum(
        _build("ledger_root", phase) for phase in PHASES)
    for phase in PHASES:
        assert _under(phase=phase) == pytest.approx(_build(phase=phase),
                                                    rel=1e-9, abs=1e-12)


def test_a_build_nothing_encloses_is_nobodys_until_something_does(ledger):
    time.sleep(0.07)        # older arrivals are not inside what follows
    build.on_duration(TRACE, 0.03125, fun_name="loner")
    build.on_duration(BACKEND, 0.0625, fun_name="jit(loner)")
    assert _under(build.NOBODY, "trace") == 0.03125
    assert _under(build.NOBODY, "compile") == 0.0625
    t0 = time.perf_counter()
    time.sleep(0.02)
    build.on_duration(TRACE, 0.015625, fun_name="held")
    build.first_call("late_label", t0, time.perf_counter() - t0)
    assert _under("first_run:late_label", "trace") == 0.015625
    assert _under(build.NOBODY, "trace") == 0.03125     # before t0: stays
    assert _under(build.NOBODY, "first_run") == _build("late_label",
                                                       "first_run") > 0


def test_unclaimed_arrivals_fold_and_stay_nobodys(ledger, monkeypatch):
    """The unclaimed history is bounded like the totals': what falls off
    its end nothing can claim any more, and the phases still add up."""
    monkeypatch.setattr(build, "_KEEP", 8)
    seen = {}

    def on_a_thread_of_its_own():
        t0 = time.perf_counter()
        for _ in range(20):
            build.on_duration(BACKEND, 0.5, fun_name="jit(folded)")
        seen["kept"] = len(build._mine.loose_at)
        build.first_call("folded_label", t0, time.perf_counter() - t0)

    thread = threading.Thread(target=on_a_thread_of_its_own)
    thread.start()
    thread.join(timeout=30)
    assert not thread.is_alive()
    assert seen["kept"] <= 9
    kept = _under("first_run:folded_label", "compile")
    assert kept == 0.5 * seen["kept"] < 10.0
    assert _under(build.NOBODY, "compile") == 10.0 - kept
    assert _under(phase="compile") == _build(phase="compile") == 10.0


def test_a_pools_one_entry_goes_under_the_weights_init_that_opened_it(
        ledger):
    from concurrent.futures import ThreadPoolExecutor

    X.block_until_ready()
    programs = [_tiny_program(_salt()) for _ in range(3)]
    with telemetry.span("request", trace_id="exec_pool"):
        with build.weights_span("init", "pooled-model"):
            with build.pooled_builds("ledger_pool") as opener, \
                    ThreadPoolExecutor(3, initializer=build.in_pool,
                                       initargs=(opener,)) as pool:
                for out in pool.map(lambda program: program(X), programs):
                    out.block_until_ready()
                time.sleep(0.06)
    # its threads record no phase of their own: one entry, the pool's wall
    assert _under("weights.init:pooled-model", "compile") == _build(
        "ledger_pool", "compile") >= 0.06
    assert _under("weights.init:pooled-model") == _build("ledger_pool")
    assert _under("ledger_pool") == 0 and _under("ledger_tiny") == 0
    # ... and a compile each on the other family, summed over the threads
    assert _cold("ledger_tiny") > 0 and _cold("ledger_pool") == 0
    # the timeline: the pool's wall, under the span that opened the pool
    spans = _build_spans("exec_pool")
    weights, = [s for s in telemetry.SPAN_STORE.spans("exec_pool")
                if s["name"] == "weights.init"]
    pooled, = [s for s in spans if s["attrs"].get("outcome") == "pooled"]
    assert pooled["attrs"]["program"] == "ledger_pool"
    assert {s["parent_id"] for s in spans} == {weights["span_id"]}
    assert all("warm" not in s["attrs"] for s in spans)
    for s in spans:                 # a thread's own builds, where >= 50 ms
        if s is not pooled:
            assert s["attrs"]["thread"].startswith("ThreadPoolExecutor")
            assert s["attrs"]["program"] == "ledger_tiny"


def test_a_jitted_lambda_is_named_by_the_line_that_called_it(ledger):
    X.block_until_ready()
    anonymous = jax.jit(lambda x: jnp.sin(x) - _salt())
    line = anonymous(X).block_until_ready() is None or \
        test_a_jitted_lambda_is_named_by_the_line_that_called_it \
        .__code__.co_firstlineno + 3
    name = f"<lambda>@tests/test_setup_ledger.py:{line}"
    for phase in ("trace", "lower", "compile"):
        assert _build(name, phase, "count") == 1, (
            phase, sorted(_series(tm.PROGRAM_BUILD_SECONDS)))
    assert _cache(name) == {"miss": 1.0}
    assert not any(p == "<lambda>" for p, _ in
                   _series(tm.PROGRAM_BUILD_SECONDS))


def test_a_lambda_flax_calls_is_named_with_the_line_of_this_package(
        ledger):
    """A frame in flax is not who asked: the first frame of THIS package
    below it is. Frames are faked by file name: the walk reads nothing
    else of them."""
    import flax

    from comfyui_distributed_tpu.models import unet

    def frame_in(filename):
        scope = {}
        exec(compile("def call(*chain):\n    return chain[0](*chain[1:])\n",
                     filename, "exec"), scope)
        return scope["call"]

    scope_param = frame_in(os.path.join(os.path.dirname(flax.__file__),
                                        "core", "scope.py"))
    jit_call = frame_in(os.path.join(os.path.dirname(jax.__file__), "_src",
                                     "pjit.py"))
    forward = frame_in(unet.__file__)
    assert forward(scope_param, jit_call, build._named, "<lambda>") == \
        "<lambda>@flax/core/scope.py:2<models/unet.py:2"
    assert scope_param(jit_call, build._named, "") == \
        "unnamed@flax/core/scope.py:2"          # nobody of ours below it
    assert forward(jit_call, build._named, "<lambda>") == \
        "<lambda>@models/unet.py:2"


def test_the_33rd_call_site_is_other(ledger, monkeypatch):
    sites = iter((f"models/unet.py:{n}", "") for n in range(1, 40))
    monkeypatch.setattr(build, "_call_site", lambda: next(sites))
    names = [build._named("<lambda>") for _ in range(build.MAX_SITES + 1)]
    assert names[0] == "<lambda>@models/unet.py:1"
    assert names[build.MAX_SITES - 1] == f"<lambda>@models/unet.py:{build.MAX_SITES}"
    assert names[build.MAX_SITES] == "<lambda>@other"
    # a site already named keeps its name; past the cap flax's own line is
    # still said (whose the lambda is), without the model's
    monkeypatch.setattr(build, "_call_site",
                        lambda: ("models/unet.py:7", ""))
    assert build._named("") == "unnamed@models/unet.py:7"
    monkeypatch.setattr(build, "_call_site", lambda: (
        "flax/core/scope.py:951<models/unet.py:99", "flax/core/scope.py:951"))
    assert build._named("<lambda>") == "<lambda>@flax/core/scope.py:951"
    # ... and a program with a name is never walked
    monkeypatch.setattr(build, "_call_site", lambda: 1 / 0)
    assert build._named("jit(seg_body)") == "seg_body"


def test_a_hit_stands_for_the_compile_its_entry_holds(ledger):
    build.on_event(LOOKUP)
    build.on_event(HIT)
    build.on_duration(SAVED, 76.5)
    build.on_duration(RETRIEVAL, 1.5)
    build.on_duration(BACKEND, 1.75, fun_name="jit(read_back)")
    assert _cold("read_back") == 78.0           # saved + retrieval
    build.on_event(LOOKUP)
    build.on_event(MISS)
    build.on_duration(BACKEND, 3.0, fun_name="jit(compiled)")
    build.on_duration(BACKEND, 0.5, fun_name="jit(never_looked_up)")
    assert _cold("compiled") == 3.0 and _cold("never_looked_up") == 0.5
    # what one hit saved names no later program
    build.on_event(LOOKUP)
    build.on_event(HIT)
    build.on_duration(SAVED, 9.0)
    build.on_event(LOOKUP)
    build.on_duration(BACKEND, 0.25, fun_name="jit(after)")
    assert _cold("after") == 0.25
    # on a pool's thread too: seconds by program, not the pool's wall
    done = threading.Event()

    def on_a_pools_thread():
        build.in_pool()
        build.on_event(LOOKUP)
        build.on_event(HIT)
        build.on_duration(SAVED, 4.0)
        build.on_duration(RETRIEVAL, 1.0)
        build.on_duration(BACKEND, 1.25, fun_name="jit(pooled_read)")
        done.set()

    thread = threading.Thread(target=on_a_pools_thread)
    thread.start()
    thread.join(timeout=30)
    assert done.is_set()
    assert _cold("pooled_read") == 5.0
    assert _build("pooled_read") == 0


def test_a_real_hit_counts_a_whole_number_of_saved_seconds(ledger):
    """JAX's own events, end to end: an entry keeps its compile time in
    whole seconds, so a quick program's hit stands for 0 s and a miss for
    its backend seconds."""
    X.block_until_ready()
    salt = _salt()
    _tiny_program(salt)(X).block_until_ready()
    compiled = _cold("ledger_tiny")
    assert compiled == _build("ledger_tiny", "compile") > 0
    _tiny_program(salt)(X).block_until_ready()
    assert _cache("ledger_tiny") == {"miss": 1.0, "hit": 1.0}
    assert _cold("ledger_tiny") == compiled     # + int(compile seconds) = 0


def test_a_build_of_60_ms_is_a_span_and_one_of_40_ms_is_not(ledger):
    with telemetry.span("request", trace_id="exec_spans") as (_, parent):
        _fed(TRACE, 0.06, "slow_trace")
        _fed(TRACE, 0.04, "quick_trace")
        _fed(LOWER, 0.07, "jit(slow_trace)")
        build.on_event(LOOKUP)
        build.on_event(HIT)
        build.on_duration(RETRIEVAL, 0.08)
        _fed(BACKEND, 0.09, "jit(slow_trace)")
        _fed(BACKEND, 0.03, "jit(quick_trace)")
        with build.weights_span("init", "spanned"):
            _fed(BACKEND, 0.2, "jit(a_weight)")
    spans = _build_spans("exec_spans")
    assert [(s["name"], s["attrs"]["program"]) for s in spans] == [
        ("build.trace", "slow_trace"), ("build.lower", "slow_trace"),
        ("build.cache_read", "slow_trace"), ("build.compile", "a_weight")]
    trace, lower, read, weight = spans
    assert trace["duration_s"] == pytest.approx(0.06)
    assert trace["attrs"]["self_s"] == "0.060000"
    assert read["attrs"]["outcome"] == "hit"
    assert weight["attrs"]["outcome"] == "uncached"
    assert {s["attrs"]["thread"] for s in spans} == {
        threading.current_thread().name}
    # in the request's own trace, under the span that was open; a request
    # that pays for a program outside a weights.* entry is not warm
    assert trace["parent_id"] == lower["parent_id"] == parent
    assert trace["attrs"]["warm"] == read["attrs"]["warm"] == "false"
    assert "warm" not in weight["attrs"]
    # the sums hold the short ones too
    assert _build("quick_trace", "trace") == 0.04


def test_a_boots_builds_are_spans_of_the_boot_trace(ledger):
    with build.boot_phase("backend"):
        _fed(BACKEND, 0.3, "jit(at_boot)")
    _fed(BACKEND, 0.3, "jit(with_no_trace)")     # a script: sums, no span
    (span,) = _build_spans(build.BOOT_TRACE)
    assert span["attrs"]["program"] == "at_boot"
    assert "warm" not in span["attrs"]
    assert _under("boot.backend", "compile") == 0.3
    assert _under(build.NOBODY, "compile") == 0.3
    assert list(telemetry.SPAN_STORE._traces) == [build.BOOT_TRACE]


def test_with_telemetry_off_no_owner_no_site_no_span(ledger, monkeypatch):
    telemetry.set_enabled(False)
    monkeypatch.setattr(build, "_call_site", lambda: 1 / 0)  # never walked
    waiting = len(build._mine.loose_at)
    with telemetry.use_trace("exec_off"):
        build.on_event(LOOKUP)
        build.on_event(HIT)
        build.on_duration(SAVED, 5.0)
        _fed(TRACE, 0.5, "<lambda>")
        _fed(BACKEND, 0.5, "jit(<lambda>)")
        jax.jit(lambda x: x + _salt())(X).block_until_ready()
        with build.pooled_builds("off") as opener:
            assert opener == (None, None)
    assert build._mine.outcome is None and build._mine.built is None
    assert len(build._mine.loose_at) == waiting
    telemetry.set_enabled(True)
    for metric in (tm.PROGRAM_COLD_COMPILE_SECONDS,
                   tm.PROGRAM_BUILD_SECONDS):
        assert metric.series() == []
    assert _under() == 0            # read while on: nobody's six, all 0
    assert not telemetry.SPAN_STORE._traces
    telemetry.set_enabled(False)    # ... and a read while off makes none
    telemetry.REGISTRY.reset()
    assert tm.PROGRAM_BUILD_UNDER_SECONDS.series() == []


def test_jax_itself_feeds_the_timeline_through_the_third_listener(ledger):
    from jax._src import monitoring

    assert build.on_time_span in monitoring.get_event_time_span_listeners()

    def ledger_slow_to_trace(x):
        time.sleep(0.06)
        return jnp.sin(x) + _salt()

    X.block_until_ready()
    with telemetry.span("request", trace_id="exec_real"):
        jax.jit(ledger_slow_to_trace)(X).block_until_ready()
    traced, = [s for s in _build_spans("exec_real")
               if s["name"] == "build.trace"]
    assert traced["attrs"]["program"] == "ledger_slow_to_trace"
    assert traced["duration_s"] >= 0.06
    assert float(traced["attrs"]["self_s"]) <= traced["duration_s"]
    assert all(s["attrs"]["program"] == "ledger_slow_to_trace"
               for s in _build_spans("exec_real"))  # jnp's own are too short
