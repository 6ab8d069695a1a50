"""NamedSharding helpers and host→device placement.

Thin, convention-setting wrappers: batch axis 0 shards over ``dp`` (the
reference's job fan-out), weights replicate (or shard over ``tp`` when tensor
parallelism is enabled), everything else replicates.

Who places weights, and when. A weight tree is never resharded inside a
program call. A program that reads its weights replicated (``shard_map``
with the weights under ``P()``) is bound to them with
``diffusion.pipeline.bind_weights(..., mesh=mesh)``, which calls
:func:`replicate` ONCE, when the program is built: the leaves are copied
to the mesh's other devices then, every program of that mesh shares the
one placed copy, and a call transfers nothing. The tensor-parallel
programs place theirs with ``parallel/tensor.shard_params``; the language
model's plain ``jax.jit`` programs run where their weights are.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import constants


def mesh_cache_key(mesh: Mesh) -> tuple:
    """Value key for a mesh: axis names + shape + device ids.

    ``id(mesh)`` is wrong here — ids are recycled after GC, so a
    long-lived controller could be handed a stale compiled fn for a
    *different* mesh with a coincident id. Shared by every pipeline's
    compile cache and by :func:`replicate`."""
    return (tuple(mesh.axis_names), tuple(mesh.shape.values()),
            tuple(d.id for d in mesh.devices.flat))


def batch_sharding(mesh: Mesh, ndim: int, axis: str = constants.AXIS_DATA) -> NamedSharding:
    """Shard dim 0 over ``axis``, replicate the rest."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, tree: Any, axis: str = constants.AXIS_DATA) -> Any:
    """Place a pytree on the mesh with leaf dim 0 sharded over ``axis``."""
    return jax.tree.map(
        lambda x: jax.device_put(x, batch_sharding(mesh, x.ndim, axis)), tree
    )


# (id(source leaf), mesh key) -> (weakref(source), weakref(placed copy)).
# Weak on both sides: a placed leaf lives exactly as long as something is
# bound to it (a program in a pipeline's compile cache), and a parameter
# that was replaced (a LoRA-patched bundle) is a new object and a new key.
_PLACED: "dict[tuple, tuple]" = {}
_PLACED_LOCK = threading.Lock()


def _placed_copy(leaf, mesh_key: tuple):
    """The live placed copy of ``leaf`` on that mesh, or None."""
    source, placed = _PLACED.get((id(leaf), mesh_key), (None, None))
    return placed() if source is not None and source() is leaf else None


def replicate(mesh: Mesh, tree: Any) -> Any:
    """``tree`` with every leaf replicated over ``mesh``, placed at most
    once per (leaf, mesh).

    Per leaf, by what can be seen of it: a leaf whose sharding is already
    equivalent to the mesh's replicated one (a one-device mesh and a leaf
    on that device; a leaf placed before) is returned AS IT IS — the same
    object, no copy, no second buffer; a leaf some live program of this
    mesh is already bound to gives that placed copy; any other leaf is
    transferred now (one ``weights.place`` span around the one batched
    ``device_put``; the shard on a device that held the leaf shares its
    buffer). Each call counts once in
    ``cdt_weight_placement_total{outcome}``: ``placed`` if it transferred
    a leaf, else ``reused`` if it found one, else ``identity``.

    Nothing is placed where nothing can be held: an abstract leaf (a
    ``ShapeDtypeStruct``) stays as it is, and so does the whole tree on a
    mesh of described devices (an off-chip compile for a
    ``jax.experimental.topologies`` topology: its client has no local
    device)."""
    if not mesh.devices.flat[0].client.local_devices():
        return tree
    from .. import telemetry
    from ..telemetry.build import weights_span

    sh = replicated_sharding(mesh)
    mesh_key = mesh_cache_key(mesh)
    leaves, treedef = jax.tree.flatten(tree)
    move, found, moved_bytes = [], 0, 0
    with _PLACED_LOCK:
        for key in [k for k, (_, p) in _PLACED.items() if p() is None]:
            del _PLACED[key]
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, jax.ShapeDtypeStruct) or (
                    isinstance(leaf, jax.Array)
                    and leaf.sharding.is_equivalent_to(sh, leaf.ndim)):
                continue
            placed = _placed_copy(leaf, mesh_key)
            if placed is None:
                move.append(i)
            else:
                leaves[i], found = placed, found + 1
        if move:
            with weights_span("place", "", leaves=len(move),
                              devices=mesh.devices.size):
                moved = jax.block_until_ready(
                    jax.device_put([leaves[i] for i in move], sh))
            for i, placed in zip(move, moved):
                source, leaves[i] = leaves[i], placed
                held = (source.devices() if isinstance(source, jax.Array)
                        else set())
                moved_bytes += placed.nbytes * len(
                    set(mesh.devices.flat) - held)
                try:
                    _PLACED[(id(source), mesh_key)] = (
                        weakref.ref(source), weakref.ref(placed))
                except TypeError:   # a Python scalar: placed, not shared
                    pass
    if telemetry.enabled():
        from ..telemetry import metrics as _tm

        _tm.WEIGHT_PLACEMENT.labels(
            outcome="placed" if move else "reused" if found
            else "identity").inc()
        _tm.WEIGHT_PLACEMENT_BYTES.inc(moved_bytes)
    return jax.tree.unflatten(treedef, leaves)


def batch_spec(ndim: int, axis: str = constants.AXIS_DATA) -> P:
    return P(axis, *([None] * (ndim - 1)))
