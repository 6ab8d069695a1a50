"""Sequence-parallel attention correctness: ring and Ulysses must equal
dense attention exactly (float32) on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from comfyui_distributed_tpu.ops.attention import (
    full_attention,
    ring_attention,
    ulysses_attention,
)
from comfyui_distributed_tpu.parallel import build_mesh

pytestmark = pytest.mark.slow  # compile-heavy: builds/jits real model stacks


def qkv(B=2, N=32, H=8, D=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (B, N, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def dense_reference(q, k, v):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def test_full_attention_matches_manual():
    q, k, v = qkv()
    np.testing.assert_allclose(
        np.asarray(full_attention(q, k, v)),
        np.asarray(dense_reference(q, k, v)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_ring_attention_exact(n_shards):
    mesh = build_mesh({"sp": n_shards})
    q, k, v = qkv()
    want = np.asarray(dense_reference(q, k, v))

    f = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None),
    ))
    got = np.asarray(f(q, k, v))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_ulysses_attention_exact(n_shards):
    mesh = build_mesh({"sp": n_shards})
    q, k, v = qkv()
    want = np.asarray(dense_reference(q, k, v))

    f = jax.jit(shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None),
    ))
    got = np.asarray(f(q, k, v))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ring_attention_long_sequence_stability():
    """Large-magnitude logits must not overflow the streaming softmax."""
    mesh = build_mesh({"sp": 4})
    q, k, v = qkv(B=1, N=64, H=4, D=8, seed=3)
    q = q * 30.0  # extreme logits
    want = np.asarray(dense_reference(q, k, v))
    f = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None),
    ))
    got = np.asarray(f(q, k, v))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("blk", [4, 8])
def test_ring_attention_subblocked_exact(monkeypatch, blk):
    """CDT_RING_BLOCK scans each hop's K/V in sub-blocks so the per-hop
    logits transient is bounded at video scale — same streaming-softmax
    identity, so the result still equals dense attention."""
    monkeypatch.setenv("CDT_RING_BLOCK", str(blk))
    mesh = build_mesh({"sp": 2})
    q, k, v = qkv()            # 16-length shards → 4 (or 2) sub-blocks
    want = np.asarray(dense_reference(q, k, v))
    f = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None),
    ))
    np.testing.assert_allclose(np.asarray(f(q, k, v)), want,
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_subblock_indivisible_tail(monkeypatch):
    """A block length that doesn't divide the hop walks full blocks plus
    one remainder tail block — the memory bound holds for every hop
    length (16-length shards at blk=7: 2 full blocks + a 2-tail)."""
    monkeypatch.setenv("CDT_RING_BLOCK", "7")
    mesh = build_mesh({"sp": 2})
    q, k, v = qkv()
    want = np.asarray(dense_reference(q, k, v))
    f = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None),
    ))
    np.testing.assert_allclose(np.asarray(f(q, k, v)), want,
                               rtol=1e-5, atol=1e-5)
