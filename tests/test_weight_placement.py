"""Weights are placed on a mesh once per (leaf, mesh) and never resharded
inside a call (PR 31, ``parallel/sharding.replicate`` through
``bind_weights(..., mesh=)``).

Held here, for both served lanes on the virtual 8-device CPU host: on a
four-device ``dp`` mesh every program of one (pipeline, mesh) is bound to
the SAME placed leaves, a second request moves no weight, and the images
are those of the unplaced weights, bit for bit; on a one-device mesh the
programs are bound to the pipeline's own leaves and nothing is copied.
"""

import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.parallel import build_mesh
from comfyui_distributed_tpu.parallel.sharding import (replicate,
                                                       replicated_sharding)
from comfyui_distributed_tpu.telemetry import metrics as tm

OUTCOMES = ("placed", "reused", "identity")


def _mesh(n_dp: int):
    return build_mesh({"dp": n_dp}, devices=jax.devices()[:n_dp])


def _counts() -> dict:
    counts = {o: tm.WEIGHT_PLACEMENT.labels(outcome=o).value
              for o in OUTCOMES}
    counts["bytes"] = tm.WEIGHT_PLACEMENT_BYTES.labels().value
    return counts


def _gained(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts().items() if v != before[k]}


class _Lane:
    """One served lane over its tiny model: the pipeline, a request's
    conditioning, the segments a request runs and what follows the carry
    in a segment call."""

    def __init__(self, pipe, spec, cond, fns, request, segments, tail=()):
        self.pipe, self.spec, self.cond = pipe, spec, cond
        self.fns = lambda mesh: fns(mesh, spec)
        self.request = lambda mesh: request(mesh, spec, 3, *cond)
        self.segments, self.tail = segments, tail
        # two lengths bound, the request's among them
        self.lengths = (2, segments[0][1])

    def by_hand(self, fns, weights):
        """The lane's own loop over the bare programs, handed ``weights``."""
        args = (jax.random.key(3),) + self.cond
        carry = fns["prep"].jitted(weights, *args)
        for start, length in self.segments:
            carry, _, _ = fns["seg"](length).jitted(
                weights, *args, jnp.int32(start), carry, *self.tail)
        return fns["fin"].jitted(weights, carry)

    def seg_call(self, fns, mesh, weights=None):
        """One bare segment call (with the weights it is bound to, or with
        ``weights``) whose small arguments are on the mesh already, so
        that a weight is all the call could still move."""
        put = lambda tree: jax.device_put(tree, replicated_sharding(mesh))
        args = put((jax.random.key(3),) + self.cond)
        carry = fns["prep"](*args)
        seg = fns["seg"](self.segments[0][1])
        return lambda: seg.jitted(weights or seg.weights, *args,
                                  put(jnp.int32(0)), carry, *self.tail)


def _unet_lane() -> _Lane:
    from test_segment_progress import _build_unet_lane

    lane = _build_unet_lane()               # 5 steps: segments of 3 and 2
    y = jnp.zeros((1, max(lane.pipe.unet.config.adm_in_channels, 1)))
    return _Lane(
        lane.pipe, lane.spec, (lane.ctx, lane.unc, y, y),
        lane.pipe.preemptible_fns,
        lambda *a: lane.pipe.generate_preemptible(
            *a, segment_steps=3)["images"],
        segments=((0, 3), (3, 2)))


def _flow_lane() -> _Lane:
    from comfyui_distributed_tpu.diffusion.pipeline_flow import FlowSpec
    from test_segment_progress import _build_flow_lane

    lane = _build_flow_lane()
    return _Lane(
        lane.pipe, FlowSpec(height=16, width=16, steps=4),
        (lane.ctx, lane.pooled), lane.pipe.segment_fns,
        lane.pipe.generate_segmented,
        segments=((0, 4),), tail=((),))     # one segment converts for itself


@pytest.fixture(scope="module", params=[_unet_lane, _flow_lane],
                ids=["unet", "flow"])
def lane(request):
    return request.param()


def _programs(lane, fns) -> list:
    bound = [fns["prep"], fns["fin"]] + [fns["seg"](n) for n in lane.lengths]
    if fns.get("cast"):
        bound.append(fns["cast"])
    return bound


def test_second_request_moves_no_weight(lane):
    mesh = _mesh(4)
    lane.request(mesh)
    fns = lane.fns(mesh)
    want = replicated_sharding(mesh)
    for fn in _programs(lane, fns):
        for leaf in jax.tree.leaves(fn.weights):
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
    before = _counts()
    lane.request(mesh)
    assert set(_gained(before)) <= {"reused"}
    # the strictest form: with the small arguments on the mesh already, a
    # bare call that had to move a weight between devices would raise
    call = lane.seg_call(fns, mesh)
    with jax.transfer_guard_device_to_device("disallow"):
        jax.block_until_ready(call())
    assert set(_gained(before)) <= {"reused"}
    # ... as the pipeline's own leaves, handed to the same program, do
    unplaced = lane.seg_call(fns, mesh, weights=lane.pipe._weights())
    with pytest.raises(Exception, match="Disallowed device-to-device"):
        with jax.transfer_guard_device_to_device("disallow"):
            jax.block_until_ready(unplaced())


def test_one_placed_copy_for_every_program(lane):
    mesh = _mesh(4)
    first, *others = _programs(lane, lane.fns(mesh))
    placed = jax.tree.leaves(first.weights)
    own = jax.tree.leaves(lane.pipe._weights())
    assert len(placed) == len(own)
    want = replicated_sharding(mesh)
    for leaf, source in zip(placed, own):
        assert leaf is not source
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
        assert len(leaf.devices()) == 4
    for fn in others:
        for leaf, same in zip(jax.tree.leaves(fn.weights), placed):
            assert leaf is same
    # and asked again, a placed leaf is its own placement
    again = replicate(mesh, first.weights)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), placed))


def test_one_device_mesh_is_the_identity(lane):
    before = _counts()
    mesh = _mesh(1)
    own = jax.tree.leaves(lane.pipe._weights())
    for fn in _programs(lane, lane.fns(mesh)):
        leaves = jax.tree.leaves(fn.weights)
        assert len(leaves) == len(own)
        assert all(leaf is source for leaf, source in zip(leaves, own))
    gained = _gained(before)
    assert set(gained) == {"identity"} and gained["identity"] >= 4


def test_images_are_those_of_the_unplaced_weights(lane):
    mesh = _mesh(4)
    served = np.asarray(lane.request(mesh))
    assert served.shape[0] == 4 and np.isfinite(served).all()
    assert not np.array_equal(served[0], served[1])
    bare = np.asarray(lane.by_hand(lane.fns(mesh), lane.pipe._weights()))
    np.testing.assert_array_equal(served, bare)


def test_a_replaced_parameter_is_placed_anew_and_a_dropped_one_freed():
    mesh = _mesh(4)
    tree = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))}
    before = _counts()
    first = replicate(mesh, tree)
    assert _gained(before) == {"placed": 1, "bytes": 3 * (48 + 16)}
    # another tree sharing a leaf shares its placed copy; the new leaf moves
    patched = {"w": tree["w"], "b": tree["b"] * 2}
    second = replicate(mesh, patched)
    assert second["w"] is first["w"] and second["b"] is not first["b"]
    np.testing.assert_array_equal(np.asarray(second["b"]), 2.0)
    # nothing bound to the placed copies any more: they are not kept
    before = _counts()
    del first, second
    gc.collect()
    third = replicate(mesh, tree)
    assert _gained(before) == {"placed": 1, "bytes": 3 * (48 + 16)}
    assert len(third["w"].devices()) == 4
