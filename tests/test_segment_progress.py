"""The served lanes stream progress with no host callback (PR 27).

``TPUTxt2Img``'s preemptible lane and ``TPUFlowTxt2Img`` in ``dp`` mode run
callback-free segment programs: a segment's last x0 and sigma leave the
program as ordinary outputs and the host hands them to the tracker when the
segment is over. Held here: nothing those lanes compile carries a callback,
so a second process reads the programs back from the persistent cache; the
flow triple is bit-identical to the one program; the tracker sees what it
saw before, at segment granularity, through both nodes; and the preview is
an output, never a carry leaf.
"""

import json
import os
import subprocess
import sys
import threading
import types
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.cluster.progress import ProgressTracker
from comfyui_distributed_tpu.diffusion import progress as events
from comfyui_distributed_tpu.diffusion.checkpoint import (LatentCheckpoint,
                                                          PreemptedError)
from comfyui_distributed_tpu.diffusion.progress import (DenoiserTap,
                                                        deliver_segment,
                                                        segment_calls,
                                                        total_calls)
from comfyui_distributed_tpu.diffusion.samplers import (PROGRAMS,
                                                        carry_structure,
                                                        equal_segment_steps,
                                                        make_program,
                                                        run_segment)
from comfyui_distributed_tpu.graph import NODE_REGISTRY
from comfyui_distributed_tpu.parallel import build_mesh

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def tracker():
    events.set_sink(None)
    t = ProgressTracker()
    yield t
    t.close()
    events.set_sink(None)


def _mesh(n_dp: int):
    return build_mesh({"dp": n_dp}, devices=jax.devices()[:n_dp])


def _build_unet_lane():
    from comfyui_distributed_tpu.diffusion.pipeline import (GenerationSpec,
                                                            Txt2ImgPipeline)
    from comfyui_distributed_tpu.models.text import (TextEncoder,
                                                     TextEncoderConfig)
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    model, params = init_unet(UNetConfig.tiny(), jax.random.key(0),
                              sample_shape=(8, 8, 4), context_len=16)
    vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                               image_hw=(16, 16))
    enc = TextEncoder(TextEncoderConfig.tiny()).init(jax.random.key(2))
    pipe = Txt2ImgPipeline(model, params, vae)
    ctx, _ = enc.encode(["a segment"])
    unc, _ = enc.encode([""])
    spec = GenerationSpec(height=16, width=16, steps=5, guidance_scale=2.0)
    return types.SimpleNamespace(pipe=pipe, ctx=ctx, unc=unc, spec=spec)


def _build_flow_lane():
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowPipeline
    from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    cfg = DiTConfig.tiny()
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                               image_hw=(16, 16))
    return types.SimpleNamespace(
        pipe=FlowPipeline(model, params, vae),
        ctx=jnp.full((1, 6, cfg.context_dim), 0.1),
        pooled=jnp.full((1, cfg.pooled_dim), 0.1))


@pytest.fixture(scope="module")
def unet_lane():
    return _build_unet_lane()


@pytest.fixture(scope="module")
def flow_lane():
    return _build_flow_lane()


# --- the arithmetic -----------------------------------------------------------


@pytest.mark.parametrize("n_steps,at_most,length", [
    (28, 8, 7), (8, 8, 8), (30, 8, 8), (30, 10, 10), (5, 2, 2), (1, 8, 1),
    (9, 8, 5)])
def test_equal_segment_steps(n_steps, at_most, length):
    assert equal_segment_steps(n_steps, at_most) == length
    assert length <= at_most
    # no more segments than the knob's own cut would make
    assert -(-n_steps // length) == -(-n_steps // at_most)


@pytest.mark.parametrize("sampler", ["euler", "heun", "dpmpp_2m", "res_2s"])
@pytest.mark.parametrize("steps,seg", [(30, 8), (28, 7), (8, 8), (1, 1),
                                       (5, 2)])
def test_segment_calls_sum_to_the_runs_total(sampler, steps, seg):
    seen, start = 0, 0
    while start < steps:
        length = min(seg, steps - start)
        seen += segment_calls(sampler, start, length, steps)
        start += length
    assert seen == total_calls(sampler, steps)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_tap_is_an_output_of_every_sampler_and_leaves_the_carry(name):
    """Every sampler's first call of a step is traced at the step's own
    level (the tap could not leave the scan otherwise), the tapped run's
    carry is the untapped run's, and the tap's row is the last step's
    sigma with that step's first x0. (To a rounding only, here: this toy
    denoiser is elementwise and fuses with the step, so the CPU compiler
    contracts the two programs differently; behind a real model the
    carry is held to the bit by the pipeline tests below.)"""
    sigmas = jnp.linspace(3.0, 0.0, 5)
    x = jax.random.normal(jax.random.key(1), (2, 4, 4, 3))
    key = jax.random.key(7)
    denoise = lambda z, sigma: z * 0.5 / (1.0 + sigma)
    plain = make_program(name, denoise, sigmas, key=key)
    want = jax.jit(lambda c: run_segment(plain, c, 1, 3))(plain.init(x))
    tap = DenoiserTap(denoise)
    tapped = make_program(name, tap, sigmas, key=key)
    got, (sigma, x0) = jax.jit(
        lambda c: run_segment(tapped, c, 1, 3, tap=tap))(tapped.init(x))
    assert len(want) == len(got)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    assert float(sigma) == float(sigmas[3])
    assert x0.shape == (1, 4, 4, 3) and np.isfinite(np.asarray(x0)).all()


def test_deliver_segment_reports_each_shard_and_counts(tracker):
    from comfyui_distributed_tpu import telemetry

    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        def fed():
            snap = telemetry.REGISTRY.snapshot()
            return {s["labels"]["source"]: s["value"] for s in snap.get(
                "cdt_progress_events_total", {}).get("series", [])}

        before = fed().get("segment", 0)
        seen = []
        deliver_segment(
            lambda sigma, x0, calls, shard: seen.append(
                (sigma, x0.shape, calls, shard)),
            jnp.float32(1.5), jnp.zeros((3, 4, 4, 2)), 7)
        assert seen == [(1.5, (1, 4, 4, 2), 7, s) for s in range(3)]
        assert fed()["segment"] - before == 3
    finally:
        telemetry.set_enabled(was)


# --- what the served lanes compile -------------------------------------------


def _unet_segment(lane, mesh, length, with_token=False):
    fns = lane.pipe.preemptible_fns(mesh, lane.spec)
    y = jnp.zeros((1, 8), jnp.float32)
    args = (jax.random.key(0), lane.ctx, lane.unc, y, y)
    operands = (jnp.int32(0), fns["prep"](*args))
    if with_token:
        operands += (jnp.int32(1),)
    fn = fns["seg"](length, with_token)
    return fn, args + operands


def _flow_segment(lane, mesh, spec, length):
    fns = lane.pipe.segment_fns(mesh, spec)
    args = (jax.random.key(0), lane.ctx, lane.pooled)
    cast = fns["cast"]() if fns["cast"] else ()
    return fns, args + (jnp.int32(0), fns["prep"](*args), cast)


def test_served_unet_segment_carries_no_host_callback(unet_lane):
    mesh = _mesh(1)
    fn, args = _unet_segment(unet_lane, mesh, 2)
    assert "callback" not in fn.jitted.lower(fn.weights, *args).as_text()
    # the control: the token form, which no lane of serve runs, has one
    fn, args = _unet_segment(unet_lane, mesh, 2, with_token=True)
    assert "callback" in fn.jitted.lower(fn.weights, *args).as_text()


def test_served_flow_programs_carry_no_host_callback(flow_lane):
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec

    mesh, spec = _mesh(1), FlowSpec(height=16, width=16, steps=4)
    fns, args = _flow_segment(flow_lane, mesh, spec, 2)
    for fn, operands in ((fns["prep"], args[:3]), (fns["cast"], ()),
                         (fns["seg"](2), args), (fns["fin"], args[-2:-1])):
        text = fn.jitted.lower(fn.weights, *operands).as_text()
        assert "callback" not in text
    # the control: the one program of generate_fn(progress=True) has one
    fn = flow_lane.pipe.generate_fn(mesh, spec, progress=True)
    assert "callback" in fn.jitted.lower(
        fn.weights, *args[:3], jnp.int32(1)).as_text()


_SECOND_PROCESS = """
import json, sys
import jax, jax.numpy as jnp
sys.path.insert(0, {root!r})
from comfyui_distributed_tpu import telemetry
telemetry.set_enabled(True)
from comfyui_distributed_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache(min_compile_secs=0.0)
sys.path.insert(0, {tests!r})
import test_segment_progress as T
from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec
mesh = T._mesh(1)
fn, args = T._unet_segment(T._build_unet_lane(), mesh, 2)
fn.jitted.lower(fn.weights, *args).compile()
lane = T._build_flow_lane()
fns, args = T._flow_segment(lane, mesh, FlowSpec(height=16, width=16, steps=4), 2)
fns["seg"](2).jitted.lower(fns["seg"](2).weights, *args).compile()
snap = telemetry.REGISTRY.snapshot()
print(json.dumps({{s["labels"]["outcome"]: s["value"] for s in
                  snap["cdt_compile_cache_requests_total"]["series"]}}))
"""


def test_a_second_process_reads_the_served_programs_from_the_cache(tmp_path):
    """What a host callback forbade: the denoise programs of both lanes
    are written to the persistent cache and a restarted process finds
    them (``cdt_compile_cache_requests_total{outcome="hit"}`` rises)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    script = _SECOND_PROCESS.format(root=str(ROOT),
                                    tests=str(ROOT / "tests"))
    runs = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-3000:]
        runs.append(json.loads(done.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold.get("miss", 0) >= 2
    assert warm.get("hit", 0) >= cold.get("miss", 0) > cold.get("hit", 0)
    assert warm.get("miss", 0) == 0


# --- the flow triple is the one program ----------------------------------------


@pytest.mark.parametrize("sampler", ["euler", "heun"])
@pytest.mark.parametrize("n_dp", [1, 2])
def test_flow_segments_are_bit_identical_to_the_one_program(
        flow_lane, monkeypatch, sampler, n_dp):
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec

    monkeypatch.setenv("CDT_PREEMPT_SEGMENT_STEPS", "2")
    mesh = _mesh(n_dp)
    spec = FlowSpec(height=16, width=16, steps=5, sampler=sampler, cfg=2.0)
    neg = dict(uncond_context=flow_lane.ctx * 0.0,
               uncond_pooled=flow_lane.pooled * 0.0)
    want = flow_lane.pipe.generate(mesh, spec, 3, flow_lane.ctx,
                                   flow_lane.pooled, **neg)
    seen = []
    got = flow_lane.pipe.generate_segmented(
        mesh, spec, 3, flow_lane.ctx, flow_lane.pooled, **neg,
        on_step=lambda sigma, x0, calls, shard: seen.append(
            (sigma, calls, shard)))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert got.shape[0] == n_dp
    # 5 steps at most 2 at a time: 2 + 2 + 1, every shard after each
    calls = [segment_calls(sampler, s, l, 5) for s, l in ((0, 2), (2, 2),
                                                          (4, 1))]
    assert [(c, s) for _, c, s in seen] == [
        (c, shard) for c in calls for shard in range(n_dp)]
    sigmas = [s for s, _, shard in seen if shard == 0]
    assert sigmas == sorted(sigmas, reverse=True)
    assert sum(calls) == total_calls(sampler, 5)


def test_flow_segments_take_the_weights_as_the_forward_pass_reads_them(
        flow_lane):
    """The tiny DiT is held in float32 and computes in bfloat16, as
    sd3-medium: every leaf but the float32 output projection is converted
    once a request by ``cast``, not once a segment program (bit-identity
    with the one program is held above)."""
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec

    spec = FlowSpec(height=16, width=16, steps=4)
    fns, args = _flow_segment(flow_lane, _mesh(1), spec, 2)
    cast = args[-1]
    held = jax.tree.leaves(flow_lane.pipe.dit_params)
    assert len(cast) == len(held) - 2          # img_out's kernel and bias
    assert {leaf.dtype for leaf in cast} == {jnp.dtype("bfloat16")}
    assert {leaf.dtype for leaf in held} == {jnp.dtype("float32")}


# --- the tracker, through both nodes -----------------------------------------


class _Watch(ProgressTracker):
    """A tracker that keeps every event it is handed."""

    def __init__(self):
        super().__init__()
        self.events = []

    def _on_event(self, token, shard, sigma, x0, calls=1):
        self.events.append((shard, sigma, calls, np.asarray(x0).shape))
        super()._on_event(token, shard, sigma, x0, calls)


@pytest.fixture
def watch():
    events.set_sink(None)
    t = _Watch()
    yield t
    t.close()
    events.set_sink(None)


class _Token:
    """What ``cluster/preemption.PreemptionToken`` is to the node."""

    def __init__(self, segment_steps, should_preempt=None, resume=None):
        self.segment_steps, self.resume = segment_steps, resume
        self.should_preempt = should_preempt or (lambda: None)
        self.resume_consumed = False


def _run_txt2img(lane, watch, prompt_id, token, sampler="euler", n_dp=2):
    node = NODE_REGISTRY["TPUTxt2Img"]()
    return node.execute(
        model=types.SimpleNamespace(pipeline=lane.pipe),
        positive={"context": lane.ctx}, negative={"context": lane.unc},
        seed=3, steps=5, cfg=2.0, width=16, height=16,
        sampler_name=sampler, mesh=_mesh(n_dp), prompt_id=prompt_id,
        progress_tracker=watch, preemption=token)[0]


def _run_flow(lane, watch, prompt_id, interrupt_event=None, n_dp=2):
    node = NODE_REGISTRY["TPUFlowTxt2Img"]()
    return node.execute(
        model=types.SimpleNamespace(pipeline=lane.pipe),
        positive={"context": lane.ctx, "pooled": lane.pooled}, seed=3,
        steps=5, width=16, height=16, mode="dp", mesh=_mesh(n_dp),
        prompt_id=prompt_id, progress_tracker=watch,
        interrupt_event=interrupt_event)[0]


def _check_whole_run(watch, prompt_id, images, sampler="euler"):
    assert images.shape[0] == 2
    snap = watch.snapshot(prompt_id)
    total = total_calls(sampler, 5)
    assert snap["done"] and not snap["failed"]
    assert snap["step"] == total and snap["fraction"] == 1.0
    assert snap["shards_reporting"] == 2
    for shard in (0, 1):
        assert watch.preview_png(prompt_id, shard=shard) is not None
    on_zero = [e for e in watch.events if e[0] == 0]
    assert [e[2] for e in on_zero] == [
        segment_calls(sampler, s, l, 5) for s, l in ((0, 2), (2, 2), (4, 1))]
    assert sum(e[2] for e in on_zero) == total       # monotonic, to the end
    sigmas = [e[1] for e in on_zero]
    assert sigmas == sorted(sigmas, reverse=True)
    assert {e[3] for e in watch.events} == {(1, 8, 8, 4)}   # one latent each


@pytest.mark.parametrize("sampler", ["euler", "heun"])
def test_txt2img_node_streams_a_preview_a_segment(unet_lane, watch, sampler):
    images = _run_txt2img(unet_lane, watch, "p1", _Token(2), sampler=sampler)
    _check_whole_run(watch, "p1", images, sampler)


def test_flow_node_streams_a_preview_a_segment(flow_lane, watch,
                                               monkeypatch):
    monkeypatch.setenv("CDT_PREEMPT_SEGMENT_STEPS", "2")
    images = _run_flow(flow_lane, watch, "p2")
    _check_whole_run(watch, "p2", images)


def _after(n_calls, answer):
    """A probe that answers (or raises) ``answer`` from its n-th call."""
    seen = []

    def probe():
        seen.append(1)
        if len(seen) < n_calls:
            return None
        if isinstance(answer, BaseException):
            raise answer
        return answer

    return probe


def _check_frozen(watch, prompt_id, calls):
    snap = watch.snapshot(prompt_id)
    assert snap["done"] and snap["failed"]
    assert snap["step"] == calls and snap["fraction"] < 1.0
    # nothing arrives after the freeze, and nothing is made up
    assert sum(e[2] for e in watch.events if e[0] == 0) == calls


@pytest.mark.parametrize("how,error", [
    ("priority", PreemptedError), (RuntimeError("lost"), RuntimeError)])
def test_txt2img_node_freezes_on_preempt_and_on_failure(
        unet_lane, watch, how, error):
    # the first boundary after a finished segment asks; the second answers
    token = _Token(2, should_preempt=_after(2, how))
    with pytest.raises(error) as caught:
        _run_txt2img(unet_lane, watch, "p3", token)
    _check_frozen(watch, "p3", 4)
    if error is PreemptedError:
        assert caught.value.checkpoint.step == 4


@pytest.mark.parametrize("how,error", [
    (True, InterruptedError), (RuntimeError("lost"), RuntimeError)])
def test_flow_node_freezes_on_interrupt_and_on_failure(
        flow_lane, watch, monkeypatch, how, error):
    monkeypatch.setenv("CDT_PREEMPT_SEGMENT_STEPS", "2")
    event = threading.Event()
    event.is_set = _after(2, how)
    with pytest.raises(error):
        _run_flow(flow_lane, watch, "p4", interrupt_event=event)
    _check_frozen(watch, "p4", 4)


# --- the preview is an output, not carry ---------------------------------------


def test_the_preview_is_not_a_carry_leaf(unet_lane):
    mesh = _mesh(2)
    fns = unet_lane.pipe.preemptible_fns(mesh, unet_lane.spec)
    struct = carry_structure(
        unet_lane.spec.sampler,
        jax.ShapeDtypeStruct((1, 8, 8, 4), jnp.float32))
    assert len(fns["carry_shapes"]) == len(struct) == 1
    fn, args = _unet_segment(unet_lane, mesh, 2)
    carry, sigma, previews = fn(*args)
    assert tuple(tuple(leaf.shape) for leaf in carry) == fns["carry_shapes"]
    assert sigma.shape == () and previews.shape == (2, 8, 8, 4)


def test_a_checkpoint_from_before_the_change_still_restores(unet_lane):
    """A checkpoint written by the callback-carrying segment program (the
    only one the lane had before PR 27: its answer is the carry alone)
    resumes under the served program, to the bit of an uncut run."""
    mesh, spec = _mesh(2), unet_lane.spec
    fn, args = _unet_segment(unet_lane, mesh, 2, with_token=True)
    carry = fn(*args)
    y = jnp.zeros((1, 8), jnp.float32)
    identity = unet_lane.pipe.checkpoint_identity(
        mesh, spec, 0, conditioning=(unet_lane.ctx, unet_lane.unc, y, y))
    old = LatentCheckpoint.from_bytes(LatentCheckpoint(
        sampler=spec.sampler, step=2, total_steps=spec.steps,
        carry=tuple(np.asarray(leaf) for leaf in carry),
        meta=identity).to_bytes())
    resumed = unet_lane.pipe.generate_preemptible(
        mesh, spec, 0, unet_lane.ctx, unet_lane.unc, resume=old,
        segment_steps=2)
    uncut = unet_lane.pipe.generate(mesh, spec, 0, unet_lane.ctx,
                                    unet_lane.unc)
    np.testing.assert_array_equal(np.asarray(resumed["images"]),
                                  np.asarray(uncut))


def test_one_flow_segment_converts_for_itself(flow_lane, monkeypatch):
    """A run of one segment shares its converted weights with nobody:
    ``cast`` is not run (no second copy of the model is allocated) and
    the segment program converts inside, as the one program does."""
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec

    mesh, spec = _mesh(1), FlowSpec(height=16, width=16, steps=3)
    fns = flow_lane.pipe.segment_fns(mesh, spec)
    ran = []
    monkeypatch.setitem(fns, "cast", lambda: ran.append(1) or ())
    want = flow_lane.pipe.generate(mesh, spec, 5, flow_lane.ctx,
                                   flow_lane.pooled)
    got = flow_lane.pipe.generate_segmented(mesh, spec, 5, flow_lane.ctx,
                                            flow_lane.pooled)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert not ran
