"""Mamba-1's selective scan (``ops/selective_scan.py``): one token, a chunk
as ``lax.scan`` and a chunk as the Pallas kernel (in the interpreter here)
are one function, from a non-zero state, across block boundaries and past
a padded chunk's last real row — held to a recurrence written out by hand
in numpy float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import selective_scan as ss

TOL = 2e-5


def case(T, d, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 8)
    return dict(
        h0=jax.random.normal(ks[0], (d, N)),
        u=jax.random.normal(ks[1], (T, d)),
        dt=jax.nn.softplus(jax.random.normal(ks[2], (T, d)) - 2.0),
        z=jax.random.normal(ks[3], (T, d)),
        B=jax.random.normal(ks[4], (T, N)),
        C=jax.random.normal(ks[5], (T, N)),
        A=-jnp.exp(jax.random.normal(ks[6], (d, N))),
        D=jax.random.normal(ks[7], (d,)))


def by_hand(h0, u, dt, z, B, C, A, D):
    """The recurrence, a token and a channel at a time, in float64."""
    f = {k: np.asarray(v, np.float64) for k, v in dict(
        h0=h0, u=u, dt=dt, z=z, B=B, C=C, A=A, D=D).items()}
    h, ys = f["h0"].copy(), []
    for t in range(f["u"].shape[0]):
        h = np.exp(f["dt"][t][:, None] * f["A"]) * h \
            + (f["dt"][t] * f["u"][t])[:, None] * f["B"][t][None, :]
        y = h @ f["C"][t] + f["D"] * f["u"][t]
        ys.append(y * f["z"][t] / (1.0 + np.exp(-f["z"][t])))
    return np.stack(ys), h


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) <= tol * max(1.0, float(np.abs(b).max()))


def order(c):
    return (c["h0"], c["u"], c["dt"], c["z"], c["B"], c["C"], c["A"], c["D"])


def test_one_token_is_the_recurrence_written_out():
    c = case(1, 24, 4)
    y, h = ss.scan_step(c["h0"], c["u"][0], c["dt"][0], c["z"][0], c["B"][0],
                        c["C"][0], c["A"], c["D"])
    want_y, want_h = by_hand(**c)
    assert close(y, want_y[0]) and close(h, want_h)
    assert y.dtype == h.dtype == jnp.float32


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
def test_a_chunk_from_a_nonzero_state_is_the_token_loop(kernel):
    c = case(24, 64, 16, seed=1)
    y, h = ss.scan_chunk(*order(c), kernel=kernel)
    want_y, want_h = by_hand(**c)
    assert close(y, want_y) and close(h, want_h)
    h_step, ys = c["h0"], []
    for t in range(24):
        y_t, h_step = ss.scan_step(h_step, c["u"][t], c["dt"][t], c["z"][t],
                                   c["B"][t], c["C"][t], c["A"], c["D"])
        ys.append(y_t)
    assert close(y, jnp.stack(ys)) and close(h, h_step)


@pytest.mark.parametrize("block_t,block_d,unroll", [(8, 32, 8), (16, 64, 8),
                                                    (8, 64, 4), (32, 16, 8)])
def test_the_kernels_blocks_do_not_change_the_answer(block_t, block_d,
                                                     unroll):
    """Time blocks hand the state on through VMEM scratch, channel groups
    keep their own slice of it: any blocking is the unblocked scan."""
    c = case(32, 64, 8, seed=2)
    want_y, want_h = ss.scan_chunk(*order(c), kernel="lax")
    y, h = ss.selective_scan(*order(c), block_t=block_t, block_d=block_d,
                             unroll=unroll, interpret=True)
    assert close(y, want_y) and close(h, want_h)


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("n_valid", [1, 11, 16])
def test_padded_rows_leave_the_state_where_the_last_real_token_left_it(
        kernel, n_valid):
    c = case(16, 32, 4, seed=3)
    y, h = ss.scan_chunk(*order(c), n_valid=jnp.int32(n_valid), kernel=kernel)
    cut = {k: (v[:n_valid] if k in ("u", "dt", "z", "B", "C") else v)
           for k, v in c.items()}
    want_y, want_h = by_hand(**cut)
    assert close(h, want_h) and close(y[:n_valid], want_y)


# (T, d, N, selective_scan's blocks or None for scan_chunk's own). The kernel
# reads the gate IN PLACE in the two 128-lane cases (one block; several time
# and channel blocks) and slices it first in the narrow ones — blocks under
# a lane tile, and d=48, which scan_chunk takes whole
WIDE_GATES = [(16, 128, 4, (16, 128, 8)), (16, 256, 4, (8, 128, 8)),
              (32, 64, 8, (8, 32, 8)), (32, 64, 8, (16, 16, 4)),
              (24, 48, 4, None)]


@pytest.mark.parametrize("kernel", ["lax", "interpret"])
@pytest.mark.parametrize("n_valid", [None, 11])
@pytest.mark.parametrize("shape", WIDE_GATES, ids=lambda s: f"{s[0]}x{s[1]}"
                         f"{'' if s[3] is None else '.b%dx%d' % s[3][:2]}")
def test_a_gate_read_from_the_right_half_of_uz_is_the_sliced_gate(
        kernel, n_valid, shape):
    """``_mamba_chunk`` hands the ``[u | z]`` product over whole: the gate
    is its last ``d`` columns wherever they lie, bit for bit what the
    sliced ``[T, d]`` gate answers, ``y`` and state."""
    T, d, N, blocks = shape
    c = case(T, d, N, seed=6)
    uz = jnp.concatenate([jax.random.normal(jax.random.key(9), (T, d)),
                          c["z"]], 1)
    wide = dict(c, z=uz)
    if blocks is None or kernel == "lax":
        n = None if n_valid is None else jnp.int32(n_valid)
        want = ss.scan_chunk(*order(c), n_valid=n, kernel=kernel)
        got = ss.scan_chunk(*order(wide), n_valid=n, kernel=kernel)
    else:
        if n_valid is not None:
            masked = jnp.where(jnp.arange(T)[:, None] < n_valid, c["dt"], 0.0)
            c, wide = dict(c, dt=masked), dict(wide, dt=masked)
        kw = dict(zip(("block_t", "block_d", "unroll"), blocks),
                  interpret=True)
        want = ss.selective_scan(*order(c), **kw)
        got = ss.selective_scan(*order(wide), **kw)
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.array_equal(a, b)


def test_the_gate_is_read_in_place_only_from_a_whole_lane_tiled_block(
        monkeypatch):
    """The rule on the operand's shape: ``uz`` reaches the Pallas call whole
    where the gate starts at a multiple of a 128-lane ``block_d`` (the
    served 5120 / 512), and is sliced first where it does not (the narrow
    ``d`` of these tests)."""
    from jax.experimental import pallas as pl

    seen = []

    def call(kernel, **kw):
        def run(*operands):
            seen.append((operands[2].shape, kw["in_specs"][2].index_map(1, 2)))
            return [jnp.zeros(s.shape, s.dtype) for s in kw["out_shape"]]
        return run

    monkeypatch.setattr(pl, "pallas_call", call)
    for T, d, block_d in [(16, 256, 128), (16, 256, 256), (16, 64, 32)]:
        c = case(T, d, 4)
        wide = dict(c, z=jnp.concatenate([c["u"], c["z"]], 1))
        ss.selective_scan.__wrapped__(*order(wide), block_t=8,
                                      block_d=block_d, unroll=8)
    assert seen == [((16, 512), (1, 4)), ((16, 512), (1, 3)),
                    ((16, 64), (1, 2))]


def conv_case(T, d, K=4, wide=True, seed=7):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (T, 2 * d if wide else d))
    return (jax.random.normal(ks[1], (K - 1, d)), x,
            jax.random.normal(ks[2], (K, d)), jax.random.normal(ks[3], (d,)))


def conv_by_hand(tail, x, w, b):
    """The causal depthwise convolution and its silu, in float64."""
    tail, x, w, b = (np.asarray(a, np.float64) for a in (tail, x, w, b))
    K, d = w.shape
    padded = np.concatenate([tail, x[:, :d]])
    v = sum(padded[j:j + len(x)] * w[j] for j in range(K)) + b
    return v / (1.0 + np.exp(-v))


# (T, d, causal_conv_silu's blocks or None for conv_chunk's own, x wide):
# the kernel reads the left half of [u | z] IN PLACE in the 128-lane cases
# (one block; several time blocks, which hand the last rows on through VMEM
# scratch, and channel blocks) and slices it first under a lane tile; a
# chunk of 10 rows is one block that is no whole sublane tile
CONVS = [(16, 128, (16, 128), True), (32, 256, (8, 128), True),
         (32, 64, (8, 32), True), (24, 32, (8, 32), False),
         (10, 48, None, True), (16, 32, None, False)]


@pytest.mark.parametrize("shape", CONVS, ids=lambda s: f"{s[0]}x{s[1]}"
                         f"{'' if s[2] is None else '.b%dx%d' % s[2]}"
                         f"{'.wide' if s[3] else ''}")
def test_the_convolution_kernel_is_the_convolution_written_out(shape):
    """``conv_chunk``: the Pallas kernel (in the interpreter) answers bit
    for bit what the four shifted products answer in XLA, from a non-zero
    tail, whichever half-wide or whole array the inputs come in."""
    T, d, blocks, wide = shape
    c = conv_case(T, d, wide=wide)
    want = jax.jit(ss.conv_chunk, static_argnames="kernel")(*c, kernel="lax")
    if blocks is None:
        got = ss.conv_chunk(*c, kernel="interpret")
    else:
        got = ss.causal_conv_silu(*c, block_t=blocks[0], block_d=blocks[1],
                                  interpret=True)
    assert got.shape == (T, d) and got.dtype == jnp.float32
    assert np.array_equal(got, want)
    assert close(want, conv_by_hand(*c))


def test_two_chunks_of_the_convolution_are_one_chunk():
    """The tail a chunk leaves is its last K−1 input rows: the next chunk
    from it continues the convolution."""
    tail, x, w, b = conv_case(32, 128)
    whole = ss.conv_chunk(tail, x, w, b, kernel="interpret")
    first = ss.conv_chunk(tail, x[:16], w, b, kernel="interpret")
    second = ss.conv_chunk(x[13:16, :128], x[16:], w, b, kernel="interpret")
    assert np.array_equal(jnp.concatenate([first, second]), whole)


def test_two_chunks_are_one_chunk():
    c = case(32, 32, 4, seed=4)
    whole_y, whole_h = ss.scan_chunk(*order(c), kernel="lax")
    first = {k: (v[:16] if k in ("u", "dt", "z", "B", "C") else v)
             for k, v in c.items()}
    y1, h1 = ss.scan_chunk(*order(first), kernel="interpret")
    second = {k: (v[16:] if k in ("u", "dt", "z", "B", "C") else v)
              for k, v in c.items()}
    second["h0"] = h1
    y2, h2 = ss.scan_chunk(*order(second), kernel="interpret")
    assert close(jnp.concatenate([y1, y2]), whole_y) and close(h2, whole_h)


def test_a_bfloat16_state_is_not_the_float32_state():
    """What the parity tool's ``state_bf16`` arm must be able to show: the
    state handed on in bfloat16 drifts from the float32 one by more than
    the float32 forms differ among themselves."""
    c = case(64, 32, 4, seed=5)
    _, want = ss.scan_chunk(*order(c), kernel="lax")
    h = c["h0"]
    for lo in range(0, 64, 8):
        part = {k: (v[lo:lo + 8] if k in ("u", "dt", "z", "B", "C") else v)
                for k, v in c.items()}
        part["h0"] = h.astype(jnp.bfloat16).astype(jnp.float32)
        _, h = ss.scan_chunk(*order(part), kernel="lax")
    assert not close(h, want, tol=1e-4)


def test_the_platform_picks_the_form_and_a_name_overrides_it(monkeypatch):
    from comfyui_distributed_tpu.ops import flash_attention

    seen = []
    monkeypatch.setattr(ss, "selective_scan",
                        lambda *a, **kw: seen.append(kw) or (a[1], a[0]))
    c = case(16, 32, 4)
    ss.scan_chunk(*order(c))
    assert not seen                                   # the CPU: lax
    monkeypatch.setattr(flash_attention, "_platform", lambda: "tpu")
    ss.scan_chunk(*order(c))
    assert seen == [dict(block_t=16, block_d=32, unroll=8, interpret=False)]
    big = case(512, 1024, 4)
    ss.scan_chunk(*order(big))
    assert seen[-1] == dict(block_t=ss.BLOCK_T, block_d=ss.BLOCK_D,
                            unroll=ss.UNROLL, interpret=False)


def test_the_platform_picks_the_convolutions_form_too(monkeypatch):
    from comfyui_distributed_tpu.ops import flash_attention

    seen = []
    monkeypatch.setattr(ss, "causal_conv_silu",
                        lambda *a, **kw: seen.append(kw) or a[1])
    ss.conv_chunk(*conv_case(16, 32))
    assert not seen                                   # the CPU: lax
    monkeypatch.setattr(flash_attention, "_platform", lambda: "tpu")
    ss.conv_chunk(*conv_case(16, 32))
    ss.conv_chunk(*conv_case(1024, 1024))
    assert seen == [dict(block_t=16, block_d=32, interpret=False),
                    dict(block_t=ss.CONV_BLOCK_T, block_d=ss.CONV_BLOCK_D,
                         interpret=False)]
