"""Random weights for a flax module, drawn a leaf at a time.

``jax.jit(module.init)`` is ONE program that holds every initialiser of the
model and its whole forward pass: SDXL's took 299 s to compile and 67 s to
read back from the compile cache (PERF.md §5, "where set-up goes").
:func:`draw_params` gives the same tree — every leaf bit for bit, under the
key flax itself gives it — from one small jitted :func:`draw_leaf` a distinct
(initialiser, shape, dtype, cast): a UNet's 1 700-odd leaves go through some
tens of programs, built side by side from a small pool of threads.

flax derives a parameter's key as ``fold_in(root, sha1(module path, counter))``
(``flax/core/scope.py``: ``LazyRng``), independent of every other leaf, so the
draws need no order and no program needs another's result. A compact module
declares its parameters by running, so one abstract pass over ``module.init``
stays (``jax.eval_shape``: the forward is traced, nothing is compiled or run);
during it ``Scope.param`` is watched for each leaf's initialiser, arguments
and key suffix. Keys are flax's for every module whose scopes do not split
their rngs (``nn.remat`` keeps them; ``nn.vmap``/``nn.scan`` with
``split_rngs`` would not — no model here uses those).
"""

from __future__ import annotations

import functools
import threading
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from flax.core.scope import LazyRng, Scope

from ..telemetry.build import in_pool, pooled_builds, weights_drawn

# Threads that build distinct draw programs side by side: XLA compiles
# outside the interpreter lock, and a truncated-normal draw takes the chip's
# compiler 0.3–2 s a shape — most of them under the persistent cache's
# threshold, so compiled again in every warm start (PERF.md §6, PR 56).
BUILDERS = 8


class _Leaf(NamedTuple):
    """What ``Scope.param`` was asked for: ``init_fn(key, *args, **kwargs)``
    with ``key`` the root rng folded with ``suffix``."""
    suffix: tuple
    init_fn: Callable[..., Any]
    args: tuple
    kwargs: tuple


def _static(args):
    """``args`` with every list as the tuple it means (flax's GroupNorm
    writes a shape as a list): ``draw_leaf`` takes them as static."""
    if isinstance(args, (list, tuple)):
        return tuple(map(_static, args))
    return args


_watch = threading.RLock()      # one thread at a time swaps Scope.param
_watching = threading.local()   # .leaves: the dict this thread's pass fills


@contextmanager
def _watched_params(leaves: dict):
    """While open, every parameter this thread's scopes CREATE is noted in
    ``leaves`` by path; other threads' scopes pass through untouched."""
    def param(self, name, init_fn, *init_args, unbox=True, **init_kwargs):
        value = scope_param(self, name, init_fn, *init_args, unbox=unbox,
                            **init_kwargs)
        noted = getattr(_watching, "leaves", None)
        path = noted is not None and ("params", *self.path, name)
        if path and path not in noted:
            # the key make_rng just handed out: this scope's rng, its count
            drawn = LazyRng.create(self.rngs["params"],
                                   self.rng_counters["params"])
            noted[path] = _Leaf(drawn.suffix, init_fn, _static(init_args),
                                _static(sorted(init_kwargs.items())))
        return value

    with _watch:
        scope_param = Scope.param
        before = getattr(_watching, "leaves", None)
        Scope.param, _watching.leaves = param, leaves
        try:
            yield
        finally:
            Scope.param, _watching.leaves = scope_param, before


def cast_float(tree, dtype):
    """Float leaves as ``dtype``; ``None`` leaves the tree as it is."""
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, tree)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def draw_leaf(key, init_fn, init_args, init_kwargs, param_dtype):
    """One parameter: the initialiser's draw, cast inside the program (a
    float32 leaf of a bfloat16 tree lives only here)."""
    return cast_float(init_fn(key, *init_args, **dict(init_kwargs)),
                      param_dtype)


def _one_initialiser(init_fn, seen: dict):
    """The first initialiser seen that is ``init_fn``'s code over its
    closed-over values: ``nn.initializers.normal(0.02)`` makes a new closure
    at every call site, and ``draw_leaf`` compiles once an OBJECT."""
    try:
        cells = tuple(c.cell_contents for c in init_fn.__closure__ or ())
        return seen.setdefault((init_fn.__code__, cells), init_fn)
    except (AttributeError, TypeError):     # no plain function; unhashable
        return init_fn


def draw_params(module, rng, *example_args, param_dtype=None,
                abstract: bool = False):
    """``module.init(rng, *example_args)``'s variables with float leaves as
    ``param_dtype`` — bit for bit what ``jax.jit`` of that gives — drawn by
    one :func:`draw_leaf` program a distinct (initialiser, arguments, cast).

    ``abstract=True`` returns the ``ShapeDtypeStruct`` tree instead (a
    conversion template: no draw at all). Counts the leaves drawn and the
    distinct programs they went through (``telemetry/build.weights_drawn``).
    On the device at once: the tree so far and, while the pool builds, up
    to ``BUILDERS`` leaves in the initialiser's own dtype.
    """
    leaves: dict[tuple, _Leaf] = {}

    def init_shapes(rng, *args):    # a new function: traced, never recalled
        return cast_float(module.init(rng, *args), param_dtype)

    if abstract:
        return jax.eval_shape(init_shapes, rng, *example_args)
    with _watched_params(leaves):
        shapes = jax.eval_shape(init_shapes, rng, *example_args)
    by_path, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(k.key for k in path) for path, _ in by_path]
    if set(paths) != set(leaves):
        raise NotImplementedError(
            f"{type(module).__name__}.init makes variables that are not one "
            f"array a parameter: {sorted(set(paths) ^ set(leaves))[:4]}")
    inits: dict = {}
    program = {path: (_one_initialiser(leaf.init_fn, inits), leaf.args,
                      leaf.kwargs, param_dtype)
               for path, leaf in leaves.items()}

    def draw(path):
        key = LazyRng(rng, leaves[path].suffix).as_jax_rng()
        return draw_leaf(key, *program[path])

    # the first leaf of each distinct program from the pool (its build is
    # the cost), the rest here: the program is in jit's cache by then
    firsts: dict = {}
    for path in paths:
        firsts.setdefault(program[path], path)
    with pooled_builds("draw_leaf") as opener, ThreadPoolExecutor(
            BUILDERS, initializer=in_pool, initargs=(opener,)) as pool:
        built = dict(zip(firsts.values(), pool.map(draw, firsts.values())))
    drawn = [built[path] if path in built else draw(path) for path in paths]
    weights_drawn(len(drawn), len(firsts))
    return jax.tree_util.tree_unflatten(treedef, drawn)
