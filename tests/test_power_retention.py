"""Power retention of degree 2 (``ops/power_retention.py``) against the
function as it is written down — the QUADRATIC form, a head's whole ``[T, T]``
matrix ``exp(b_t − b_s)(q_t·k_s)²`` under the causal mask, its row sums and
the quotient, with no ``φ`` and no state: ``φ``'s identity at the exact and at
the held ``D``, the step walked over the tokens, the chunk form as a ``lax``
walk and as the Pallas kernel in the interpreter at blocks that do and do not
divide the chunk, a padded chunk under a gate that is data, and the long-decay
range."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import power_retention as P

T, H, G, D_HEAD = 40, 6, 2, 8
F32_TOL = 1e-4      # relative; float32 forms read 1e-6
# a bfloat16 arm against the float32 quadratic form: φ's entries and the
# state as the read's operand carry 2^-9 each; 2e-3 read on these operands
BF16_TOL = 1.5e-2


def quadratic(q, k, v, log_g):
    """``o`` [T,H,d_v]: the function, one head at a time."""
    T, H, _ = q.shape
    J = H // k.shape[1]
    b = jnp.cumsum(log_g, axis=0)
    seen = jnp.tril(jnp.ones((T, T), bool))
    out = []
    with jax.default_matmul_precision("highest"):
        for h in range(H):
            g = h // J
            decay = jnp.exp(jnp.where(seen, b[:, None, g] - b[None, :, g],
                                      -jnp.inf))
            A = decay * (q[:, h] @ k[:, g].T) ** 2
            out.append(A @ v[:, g] / A.sum(-1, keepdims=True))
    return jnp.stack(out, axis=1)


def operands(T=T, H=H, G=G, d=D_HEAD, gate=2.0, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    scale = d ** -0.25
    return (jax.random.normal(ks[0], (T, H, d)) * scale,
            jax.random.normal(ks[1], (T, G, d)) * scale,
            jax.random.normal(ks[2], (T, G, d)),
            jax.nn.log_sigmoid(gate + jax.random.normal(ks[3], (T, G))))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def empty(G=G, d=D_HEAD):
    return jnp.zeros((G, d, P.width(d))), jnp.zeros((G, d, d))


def walked(q, k, v, log_g):
    S, Z = empty(k.shape[1], k.shape[2])
    out = []
    for t in range(q.shape[0]):
        S, Z, o = P.retention_step(S, Z, q[t], k[t], v[t], log_g[t])
        out.append(o)
    return jnp.stack(out), S, Z


@pytest.mark.parametrize("d", [8, 128])
@pytest.mark.parametrize("form", ["exact", "held"])
def test_phi_of_two_vectors_multiplies_to_the_square_of_their_product(d, form):
    a, b = jax.random.normal(jax.random.key(d), (2, 5, d))
    phi = {"exact": P.phi_exact, "held": P.phi}[form]
    want = (a * b).sum(-1) ** 2
    assert phi(a).shape[-1] == {"exact": d * (d + 1) // 2,
                                "held": P.width(d)}[form]
    assert rel((phi(a) * phi(b)).sum(-1), want) < 1e-5


def test_the_held_width_is_whole_lane_tiles_just_over_the_exact_one():
    assert P.width(128) == 8320 == 65 * 128 and P.tiles(128) == 65
    assert 128 * 129 // 2 == 8256


def test_the_step_walked_over_the_tokens_is_the_quadratic_form():
    q, k, v, log_g = operands()
    assert rel(walked(q, k, v, log_g)[0], quadratic(q, k, v, log_g)) < F32_TOL


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("block", [8, 20, 16, 7])
def test_the_chunk_form_is_the_quadratic_form_and_the_steps_state(kernel,
                                                                  block):
    """Blocks that divide the chunk (8, 20) and that do not (16, 7: the walk
    then takes their greatest common divisor with it)."""
    q, k, v, log_g = operands()
    o, S, Z = P.retention_chunk(*empty(), q, k, v, log_g, T, jnp.float32,
                                block, kernel=kernel)
    _, S_want, Z_want = walked(q, k, v, log_g)
    assert rel(o, quadratic(q, k, v, log_g)) < F32_TOL
    assert rel(S, S_want) < F32_TOL and rel(Z, Z_want) < F32_TOL


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_a_chunk_goes_on_from_the_state_before_it(kernel):
    q, k, v, log_g = operands()
    at = 24
    _, S, Z = P.retention_chunk(*empty(), q[:at], k[:at], v[:at], log_g[:at],
                                at, jnp.float32, 8, kernel=kernel)
    o, _, _ = P.retention_chunk(S, Z, q[at:], k[at:], v[at:], log_g[at:],
                                T - at, jnp.float32, 8, kernel=kernel)
    assert rel(o, quadratic(q, k, v, log_g)[at:]) < F32_TOL


def test_the_kernel_in_the_interpreter_is_the_lax_walk():
    q, k, v, log_g = operands(seed=3)
    got = [P.retention_chunk(*empty(), q, k, v, log_g, T - 3, jnp.float32, 8,
                             kernel=kernel) for kernel in ("lax", "interpret")]
    for a, b in zip(*got):
        assert rel(a[:T - 3] if a.shape[0] == T else a,
                   b[:T - 3] if b.shape[0] == T else b) < 1e-5


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_a_bfloat16_arm_is_within_its_stated_limit_and_over_float32s(kernel):
    q, k, v, log_g = operands()
    want = quadratic(q, k, v, log_g)
    o, _, _ = P.retention_chunk(*empty(), q, k, v, log_g, T, jnp.bfloat16, 8,
                                kernel=kernel)
    assert F32_TOL < rel(o, want) < BF16_TOL


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_a_padded_chunk_hands_back_the_state_of_its_last_real_row(kernel):
    """The recurrent-leaf contract under a gate that is data: the padded
    rows carry real gates and real keys, and count as gate 1 and key 0."""
    q, k, v, log_g = operands()
    n = T - 11
    o, S, Z = P.retention_chunk(*empty(), q, k, v, log_g, n, jnp.float32, 8,
                                kernel=kernel)
    _, S_want, Z_want = walked(q[:n], k[:n], v[:n], log_g[:n])
    assert rel(o[:n], quadratic(q, k, v, log_g)[:n]) < F32_TOL
    assert rel(S, S_want) < F32_TOL and rel(Z, Z_want) < F32_TOL


def test_letting_the_padded_rows_gates_through_is_seen():
    """What a mask by position alone would do — the padded rows' keys out,
    their GATES left in — decays the state that is handed on."""
    q, k, v, log_g = operands()
    n = T - 11
    _, S_want, _ = walked(q[:n], k[:n], v[:n], log_g[:n])
    keys_out = jnp.where((jnp.arange(T) < n)[:, None, None], k, 0.0)
    _, S, _ = P.retention_chunk(*empty(), q, keys_out, v, log_g, T,
                                jnp.float32, 8, kernel="lax")
    assert rel(S, S_want) > 0.1


@pytest.mark.parametrize("gate, name", [(6.9, "0.999"), (2.2, "0.9")])
@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_four_thousand_tokens_stay_finite_and_inside_the_limit(gate, name,
                                                               kernel):
    """γ ≈ 0.999 (a running log-gate of −4 by the end) and γ ≈ 0.9 (−430:
    ``exp`` of it alone is 0, of its negative overflows): every ratio is
    ``exp`` of a difference taken first."""
    T = 4096
    q, k, v, _ = operands(T=T, H=2, G=1, seed=7)
    log_g = jnp.full((T, 1), jax.nn.log_sigmoid(gate))
    assert abs(float(jnp.exp(log_g[0, 0])) - float(name)) < 6e-3
    o, S, Z = P.retention_chunk(*empty(1), q, k, v, log_g, T, jnp.float32,
                                256, kernel=kernel)
    assert bool(jnp.isfinite(o).all() & jnp.isfinite(S).all()
                & jnp.isfinite(Z).all())
    assert rel(o, quadratic(q, k, v, log_g)) < F32_TOL


def test_the_step_kernel_in_the_interpreter_is_the_lax_step():
    q, k, v, log_g = operands()
    S, Z = (jax.random.normal(jax.random.key(5), s.shape) for s in empty())
    want = P.retention_step(S, Z, q[0], k[0], v[0], log_g[0], kernel="lax")
    got = P.retention_step(S, Z, q[0], k[0], v[0], log_g[0],
                           kernel="interpret")
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-6


def test_the_step_reads_the_old_state_once_and_answers_the_new_ones_read():
    """``φ(q)ᵀS_t`` from ``S_{t−1}``: the step's one pass equals reading the
    state it hands back."""
    q, k, v, log_g = operands()
    S, Z = (jax.random.normal(jax.random.key(9), s.shape) for s in empty())
    Z = jnp.einsum("gde,gfe->gdf", Z, Z)          # symmetric, positive
    S1, Z1, o = P.retention_step(S, Z, q[0], k[0], v[0], log_g[0])
    qg = q[0].reshape(G, -1, D_HEAD)
    num = jnp.einsum("gjD,gvD->gjv", P.phi(qg), S1)
    den = jnp.einsum("gjd,gde,gje->gj", qg, Z1, qg)
    assert rel(o, (num / den[..., None]).reshape(H, -1)) < F32_TOL
