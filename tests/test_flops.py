"""Analytic FLOP counter: exact on hand-computable programs, recurses
through scan, and sees conv FLOPs that XLA's TPU cost analysis drops."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.utils.flops import estimate_flops, shape_args


class TestPrimitives:
    def test_matmul(self):
        a, b = shape_args(((8, 16), "f4"), ((16, 4), "f4"))
        # 2*M*N*K = 2*8*4*16
        assert estimate_flops(jnp.matmul, a, b) == 2 * 8 * 4 * 16

    def test_batched_einsum(self):
        f = lambda x, y: jnp.einsum("bik,bkj->bij", x, y)
        a, b = shape_args(((3, 8, 16), "f4"), ((3, 16, 4), "f4"))
        assert estimate_flops(f, a, b) == 3 * 2 * 8 * 4 * 16

    def test_conv(self):
        def f(x, k):
            return jax.lax.conv_general_dilated(
                x, k, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x, k = shape_args(((1, 8, 8, 4), "f4"), ((3, 3, 4, 16), "f4"))
        # 2 * out_elems(1*8*8*16) * k_spatial(9) * c_in(4)
        assert estimate_flops(f, x, k) == 2 * (8 * 8 * 16) * 9 * 4

    def test_grouped_conv(self):
        def f(x, k):
            return jax.lax.conv_general_dilated(
                x, k, (1, 1), "SAME", feature_group_count=4,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x, k = shape_args(((1, 8, 8, 16), "f4"), ((3, 3, 4, 16), "f4"))
        assert estimate_flops(f, x, k) == 2 * (8 * 8 * 16) * 9 * 16 / 4

    def test_scan_multiplies_by_length(self):
        w, = shape_args(((16, 16), "f4"))

        def f(w):
            def body(x, _):
                return x @ w, None
            x0 = jnp.ones((4, 16))
            out, _ = jax.lax.scan(body, x0, None, length=7)
            return out

        assert estimate_flops(f, w) == 7 * 2 * 4 * 16 * 16

    def test_elementwise_free(self):
        x, = shape_args(((128, 128), "f4"))
        assert estimate_flops(lambda x: jnp.tanh(x) + x * 2, x) == 0


def test_unet_counts_dominant_flops():
    """The tiny UNet's analytic count lands within sanity bounds and is
    dominated by convs+matmuls (a zero count would mean the walker missed
    the model's structure entirely)."""
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet

    cfg = UNetConfig.tiny()
    model, params = init_unet(cfg, jax.random.key(0), sample_shape=(8, 8, 4),
                              context_len=16)
    x, t, c, y = shape_args(
        ((1, 8, 8, 4), "f4"), ((1,), "f4"),
        ((1, 16, cfg.context_dim), "f4"),
        ((1, max(cfg.adm_in_channels, 1)), "f4"))
    flops = estimate_flops(
        lambda p, *a: model.apply(p, *a), params, x, t, c,
        y if cfg.adm_in_channels else None)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    # conv nets re-use weights spatially: flops well above 2*params,
    # below an absurd bound
    assert flops > 2 * n_params
    assert flops < 1e12


@pytest.mark.parametrize("layout", ["bh", "packed"])
def test_pallas_flash_counts_grid(layout):
    """The pallas kernel body runs once per grid step; the walker must
    multiply (missing this undercounts flash attention ~1000×). Flash
    and dense attention carry identical algorithmic FLOPs: the classic
    call by body × grid, the packed call — whose body stacks two D=64
    heads into 128-deep passes and may loop over K slabs — by the cost
    it states (``cost_estimate``)."""
    from comfyui_distributed_tpu.ops.flash_attention import flash_attention

    B, N, H, D = 1, 1024, 4, 64
    q, k, v = shape_args(((B, N, H, D), "f4"), ((B, N, H, D), "f4"),
                         ((B, N, H, D), "f4"))
    dense = estimate_flops(
        lambda q, k, v: jax.nn.dot_product_attention(q, k, v), q, k, v)
    flash = estimate_flops(
        lambda q, k, v: flash_attention(q, k, v, interpret=True,
                                        layout=layout), q, k, v)
    assert dense == 2 * 2 * B * H * N * N * D
    assert flash == dense
