"""Mesh / sharding / RNG / collective tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from comfyui_distributed_tpu.parallel import (
    MeshSpec,
    build_mesh,
    device_census,
    mesh_from_config,
    participant_key,
    participant_keys,
    seed_to_key,
    shard_batch,
)
from comfyui_distributed_tpu.parallel import collectives, mesh as mesh_mod
from comfyui_distributed_tpu.parallel.rng import participant_seeds
from comfyui_distributed_tpu.utils.exceptions import ShardingError

pytestmark = pytest.mark.slow  # compile-heavy: builds/jits real model stacks


def test_device_census_virtual_8():
    census = device_census()
    assert len(census) == 8
    assert all(d["platform"] == "cpu" for d in census)


def test_mesh_spec_resolution():
    assert MeshSpec.from_mapping({"dp": -1}).resolve(8) == (8,)
    assert MeshSpec.from_mapping({"dp": -1, "tp": 2}).resolve(8) == (4, 2)
    assert MeshSpec.from_mapping({"dp": 2, "tp": 2}).resolve(8) == (2, 2)
    assert MeshSpec.from_mapping({"dp": 3}).resolve(8) == (3,)  # subset mesh
    with pytest.raises(ShardingError):
        MeshSpec.from_mapping({"dp": -1, "tp": -1})


def test_mesh_spec_subset_and_indivisible():
    # fixed axes may use a subset of devices
    m = build_mesh({"dp": 3})
    assert m.shape == {"dp": 3}
    # -1 with indivisible fixed product fails
    with pytest.raises(ShardingError):
        MeshSpec.from_mapping({"dp": -1, "tp": 3}).resolve(8)
    with pytest.raises(ShardingError):
        MeshSpec.from_mapping({"dp": 16}).resolve(8)


def test_build_mesh_and_describe():
    m = build_mesh({"dp": 4, "tp": 2})
    assert m.axis_names == ("dp", "tp")
    d = mesh_mod.describe_mesh(m)
    assert d["axes"] == {"dp": 4, "tp": 2}
    assert d["n_devices"] == 8


def test_mesh_from_config_default():
    m = mesh_from_config({})
    assert m.shape == {"dp": 8}


def test_shard_batch_placement():
    m = build_mesh({"dp": 8})
    x = jnp.arange(16.0).reshape(16, 1)
    sx = shard_batch(m, x)
    assert sx.sharding.spec == P("dp", None)
    np.testing.assert_allclose(np.asarray(sx), np.asarray(x))


def test_participant_keys_match_in_and_out_of_mesh():
    """Host-side participant_keys must equal what participant_key yields at
    each mesh index — the contract that makes single-host replay of a
    sharded run deterministic."""
    m = build_mesh({"dp": 8})
    base = seed_to_key(42)

    def inner(_):
        k = participant_key(base, "dp")
        return jax.random.bits(k, (1, 4))

    f = shard_map(
        inner, mesh=m, in_specs=(P("dp", None),), out_specs=P("dp", None)
    )
    sharded_bits = f(jnp.zeros((8, 1)))
    host_keys = participant_keys(base, 8)
    host_bits = jax.vmap(lambda k: jax.random.bits(k, (4,)))(host_keys)
    np.testing.assert_array_equal(np.asarray(sharded_bits), np.asarray(host_bits))
    # all participants draw distinct streams
    assert len({tuple(r) for r in np.asarray(host_bits)}) == 8


def test_participant_seeds_reference_parity():
    # master keeps seed; worker N gets seed+N+1 (nodes/utilities.py:52-75)
    assert participant_seeds(100, 4) == [100, 101, 102, 103]


def test_gather_batch_order():
    """gather_batch concatenates shards in mesh-index order (master-first
    contract of the reference collector)."""
    m = build_mesh({"dp": 8})

    def inner(x):
        i = collectives.shard_index("dp")
        return collectives.gather_batch(x + i.astype(x.dtype))

    f = jax.jit(
        shard_map(
            inner, mesh=m, in_specs=(P("dp", None),), out_specs=P(None, None),
            check_vma=False,
        )
    )
    out = f(jnp.zeros((8, 2)))
    np.testing.assert_array_equal(
        np.asarray(out[:, 0]), np.arange(8, dtype=np.float32)
    )


def test_ring_shift():
    m = build_mesh({"dp": 8})

    def inner(x):
        i = collectives.shard_index("dp").astype(x.dtype)
        shifted = collectives.ring_shift(x + i, "dp", shift=1)
        return shifted

    f = jax.jit(shard_map(inner, mesh=m, in_specs=(P("dp", None),), out_specs=P("dp", None)))
    out = np.asarray(f(jnp.zeros((8, 1))))
    # shard i holds value of shard i-1 (ring)
    expected = (np.arange(8) - 1) % 8
    np.testing.assert_array_equal(out[:, 0], expected)


class TestMultihostBootstrap:
    """Bootstrap logic with a faked jax.distributed.initialize (the real
    one needs a live coordinator; the code path is identical)."""

    def _reset(self):
        from comfyui_distributed_tpu.parallel import bootstrap
        bootstrap._initialized = False
        return bootstrap

    def test_noop_without_coordinator(self, monkeypatch):
        b = self._reset()
        monkeypatch.delenv("CDT_COORDINATOR", raising=False)
        calls = []
        assert b.init_multihost(initialize_fn=lambda **kw: calls.append(kw)) is False
        assert calls == []

    def test_explicit_args_forwarded(self):
        b = self._reset()
        calls = []
        ok = b.init_multihost("10.0.0.1:9911", 4, 2,
                              initialize_fn=lambda **kw: calls.append(kw))
        assert ok is True
        assert calls == [{"coordinator_address": "10.0.0.1:9911",
                          "num_processes": 4, "process_id": 2}]
        # idempotent: second call doesn't re-initialize
        assert b.init_multihost("10.0.0.1:9911", 4, 2,
                                initialize_fn=lambda **kw: calls.append(kw))
        assert len(calls) == 1

    def test_env_fallbacks(self, monkeypatch):
        b = self._reset()
        monkeypatch.setenv("CDT_COORDINATOR", "c:1")
        monkeypatch.setenv("CDT_NUM_HOSTS", "2")
        monkeypatch.setenv("CDT_HOST_INDEX", "1")
        calls = []
        assert b.init_multihost(initialize_fn=lambda **kw: calls.append(kw))
        assert calls[0]["num_processes"] == 2 and calls[0]["process_id"] == 1

    def test_incomplete_config_raises(self, monkeypatch):
        b = self._reset()
        monkeypatch.delenv("CDT_NUM_HOSTS", raising=False)
        monkeypatch.delenv("CDT_HOST_INDEX", raising=False)
        with pytest.raises(ValueError):
            b.init_multihost("c:1", initialize_fn=lambda **kw: None)
        with pytest.raises(ValueError):
            b.init_multihost("c:1", 4, 7, initialize_fn=lambda **kw: None)
