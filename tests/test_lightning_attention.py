"""Decayed linear attention (``ops/lightning_attention.py``): one token, a
chunk in matrix products and the reference's token scan are one function —
from a non-zero state, across the chunk form's blocks, past a padded
chunk's last real row, and at decays whose ratios underflow float32 —
held to the recurrence written out by hand in numpy float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import lightning_attention as la

# float32 products summed over a chunk of a few dozen rows: 1e-6 relative
# each; 2e-5 of the largest value leaves an order of magnitude of room and
# is four orders under what a dropped decay or a bfloat16 state reads
TOL = 2e-5
SCALE = 0.3


def case(T, H, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return dict(S0=jax.random.normal(ks[0], (H, d, d)),
                q=jax.random.normal(ks[1], (T, H, d)),
                k=jax.random.normal(ks[2], (T, H, d)),
                v=jax.random.normal(ks[3], (T, H, d)))


def by_hand(S0, q, k, v, s, n=None):
    """The recurrence a token and a head at a time, in float64."""
    S = np.asarray(S0, np.float64).copy()
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    lam = np.exp(-np.asarray(s, np.float64))
    outs = []
    for t in range(q.shape[0] if n is None else n):
        S = lam[:, None, None] * S + k[t][:, :, None] * v[t][:, None, :]
        outs.append(np.einsum("hk,hkv->hv", q[t], S) * SCALE)
    return np.stack(outs), S


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


def test_the_slopes_are_lightning_attention_twos():
    s = la.slopes(32)
    assert s.dtype == np.float32 and s.shape == (32,)
    assert np.allclose(s, 2.0 ** (-8.0 * np.arange(1, 33) / 32))
    assert np.isclose(np.exp(-s[0]), 0.4313, atol=1e-4)     # fastest head
    assert np.isclose(np.exp(-s[-1]), 0.99610, atol=1e-5)   # slowest head


def test_one_token_is_the_recurrence_written_out():
    c, s = case(1, 4, 8), la.slopes(4)
    S, o = la.lightning_step(c["S0"], c["q"][0], c["k"][0], c["v"][0], s,
                             SCALE)
    want_o, want_S = by_hand(**c, s=s)
    assert close(o, want_o[0]) and close(S, want_S)
    assert S.dtype == o.dtype == jnp.float32


@pytest.mark.parametrize("block", [4, 8, 32, 256])
def test_a_chunk_from_a_nonzero_state_is_the_token_loop(block):
    c, s = case(32, 4, 8, seed=1), la.slopes(4)
    o, S = la.lightning_chunk(c["S0"], c["q"], c["k"], c["v"], s, SCALE, 32,
                              jnp.float32, block=block)
    want_o, want_S = by_hand(**c, s=s)
    assert close(o, want_o) and close(S, want_S)
    S_step, outs = c["S0"], []
    for t in range(32):
        S_step, o_t = la.lightning_step(S_step, c["q"][t], c["k"][t],
                                        c["v"][t], s, SCALE)
        outs.append(o_t)
    assert close(o, jnp.stack(outs)) and close(S, S_step)


@pytest.mark.parametrize("n_valid", [1, 7, 8, 19, 31])
@pytest.mark.parametrize("block", [8, 32])
def test_a_padded_chunk_leaves_the_state_where_its_last_real_row_did(
        n_valid, block):
    """``llm_model.chunked_prefill``'s contract for a recurrent leaf: the
    rows past ``n_valid`` add nothing and decay nothing."""
    c, s = case(32, 4, 8, seed=2), la.slopes(4)
    o, S = la.lightning_chunk(c["S0"], c["q"], c["k"], c["v"], s, SCALE,
                              jnp.asarray(n_valid), jnp.float32, block=block)
    want_o, want_S = by_hand(**c, s=s, n=n_valid)
    assert close(S, want_S) and close(o[:n_valid], want_o)
    # whatever the padded rows hold
    loud = {k: c[k].at[n_valid:].set(1e3) for k in ("q", "k", "v")}
    _, S_loud = la.lightning_chunk(c["S0"], loud["q"], loud["k"], loud["v"],
                                   s, SCALE, jnp.asarray(n_valid),
                                   jnp.float32, block=block)
    assert close(S_loud, want_S)


def test_decay_ratios_that_underflow_are_formed_as_differences_first():
    """Head 0 of 32 forgets at 0.43 a token: ``λ^−255`` is 1e93, past
    float32, and ``λ^255`` an exact 0 — a ratio made of the two is NaN, a
    ratio made as ``exp(−s·(i−j))`` is right."""
    c, s = case(512, 32, 4, seed=3), la.slopes(32)
    o, S = la.lightning_chunk(c["S0"], c["q"], c["k"], c["v"], s, SCALE, 512,
                              jnp.float32, block=256)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    want_o, want_S = by_hand(**c, s=s)
    assert close(o, want_o) and close(S, want_S)
    with np.errstate(over="ignore"):
        assert float(np.exp(np.float32(255 * s[0]))) == np.inf


def test_bfloat16_operands_keep_the_state_float32():
    c, s = case(32, 4, 8, seed=4), la.slopes(4)
    o, S = la.lightning_chunk(c["S0"], c["q"], c["k"], c["v"], s, SCALE, 32,
                              jnp.bfloat16, block=8)
    assert S.dtype == o.dtype == jnp.float32
    want_o, want_S = by_hand(**c, s=s)
    # bfloat16 operands: 2^-8 a product
    assert close(S, want_S, 2e-2) and not close(S, want_S, TOL)
    assert close(o, want_o, 5e-2)
