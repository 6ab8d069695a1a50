"""``GEGLU`` applies its one ``proj_in`` weight as a value and a gate
product (PERF.md §6, PR 35). That moves where the TPU compiler puts the
exact gelu and nothing else: the function, the parameter tree and the
seeded weights are those of the one-product form, held here as a plain
reference, bit for bit."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from comfyui_distributed_tpu.models import convert
from comfyui_distributed_tpu.models.layers import GEGLU


class OneProduct(nn.Module):
    """The form ``GEGLU`` had up to PR 34: one 8·dim-wide ``nn.Dense``,
    split, exact gelu on the second half."""

    mult: int = 4
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dim = x.shape[-1]
        h = nn.Dense(dim * self.mult * 2, dtype=self.dtype, name="proj_in")(x)
        h, gate = jnp.split(h, 2, axis=-1)
        h = h * nn.gelu(gate, approximate=False)
        return nn.Dense(dim, dtype=self.dtype, name="proj_out")(h)


SHAPES = [(dim, tokens) for dim in (64, 128) for tokens in (8, 130)]
shapes = pytest.mark.parametrize("dim,tokens", SHAPES,
                                 ids=[f"d{d}-n{n}" for d, n in SHAPES])


def _input(dim, tokens, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.key(1), (2, tokens, dim), dtype)


def _described(tree):
    return {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


@shapes
def test_the_parameter_tree_is_the_one_product_forms(dim, tokens):
    x = _input(dim, tokens)
    ours = jax.eval_shape(GEGLU().init, jax.random.key(0), x)
    theirs = jax.eval_shape(OneProduct().init, jax.random.key(0), x)
    assert _described(ours) == _described(theirs)
    assert _described(ours)["['params']['proj_in']['kernel']"] == (
        (dim, 8 * dim), jnp.float32)


@shapes
def test_one_key_gives_the_same_leaves_bit_for_bit(dim, tokens):
    x = _input(dim, tokens)
    ours = GEGLU().init(jax.random.key(7), x)
    theirs = OneProduct().init(jax.random.key(7), x)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), ours, theirs))


@shapes
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_output_equals_the_one_product_forms(dim, tokens, dtype):
    x = _input(dim, tokens)
    params = OneProduct(dtype=dtype).init(jax.random.key(0), x)
    # a zero bias would hide a bias taken from the wrong half
    params = jax.tree_util.tree_map(
        lambda p: p + 0.01 * jnp.arange(p.shape[-1], dtype=p.dtype), params)
    want = jax.jit(OneProduct(dtype=dtype).apply)(params, x)
    got = jax.jit(GEGLU(dtype=dtype).apply)(params, x)
    assert got.dtype == want.dtype == dtype
    assert float(jnp.abs(want.astype(jnp.float32)).mean()) > 0.01
    assert jnp.array_equal(got, want)


def test_three_products_each_named_under_the_ffn_scope():
    x = _input(64, 8)
    params = GEGLU().init(jax.random.key(0), x)
    eqns = jax.make_jaxpr(GEGLU().apply)(params, x).jaxpr.eqns
    stacks = [str(e.source_info.name_stack) for e in eqns
              if e.primitive.name == "dot_general"]
    assert stacks == ["GEGLU/cdt.ffn/value", "GEGLU/cdt.ffn/gate",
                      "GEGLU/cdt.ffn/proj_out"]
    # every equation of the layer is under the scope, the slices of the
    # weight included
    assert all(str(e.source_info.name_stack).startswith("GEGLU/cdt.ffn")
               for e in eqns)


def test_a_checkpoints_feed_forward_loads_through_the_converter():
    """LDM's ``ff.net.0.proj`` (a torch ``Linear`` to 8·dim, value rows
    first) and ``ff.net.2`` map onto the tree by the converter's own
    rule, and the module computes what torch's GEGLU does."""
    dim, rng = 64, np.random.default_rng(0)
    sd = {"ff.net.0.proj.weight": rng.normal(size=(8 * dim, dim)) / 8,
          "ff.net.0.proj.bias": rng.normal(size=(8 * dim,)) / 8,
          "ff.net.2.weight": rng.normal(size=(dim, 4 * dim)) / 16,
          "ff.net.2.bias": rng.normal(size=(dim,)) / 8}
    sd = {k: v.astype(np.float32) for k, v in sd.items()}
    x = np.asarray(_input(dim, 8, jnp.float32))
    module = GEGLU(dtype=jnp.float32)
    template = jax.eval_shape(module.init, jax.random.key(0), x)["params"]
    filler = convert._Filler(sd, template)
    filler.linear("ff.net.0.proj", "proj_in")
    filler.linear("ff.net.2", "proj_out")
    params = filler.finish(expect_prefix="ff.")

    got = module.apply({"params": params}, x)

    both = x @ sd["ff.net.0.proj.weight"].T + sd["ff.net.0.proj.bias"]
    value, gate = np.split(both, 2, axis=-1)
    erf = np.vectorize(math.erf)
    hidden = value * (0.5 * gate * (1.0 + erf(gate / math.sqrt(2.0))))
    want = hidden @ sd["ff.net.2.weight"].T + sd["ff.net.2.bias"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
