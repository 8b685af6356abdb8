"""Model weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in the type they are trained in.

The program's parameter tree gives only the shapes and dtypes (a tree of
``jax.ShapeDtypeStruct``); every value comes from here, so the plain
reference can make the very same weights without taking any from the
program. Norm scales (leaves whose path ends in ``norm``) start at one;
every other leaf is normal with the configuration's ``initializer_range``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.harness import seed_words


def _is_norm(path) -> bool:
    last = path[-1]
    key = getattr(last, "key", getattr(last, "name", ""))
    return str(key).endswith("norm")


def weight_builder(template, init_std: float):
    """A traceable ``build(lo, hi) -> tree`` that fills a tree shaped like
    ``template`` (``ShapeDtypeStruct`` leaves) from the two seed words: one
    key per leaf, by its position in the flattened tree."""
    paths_leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    treedef = jax.tree_util.tree_structure(template)
    norms = tuple(_is_norm(p) for p, _ in paths_leaves)
    shapes = tuple((leaf.shape, jnp.dtype(leaf.dtype)) for _, leaf in paths_leaves)

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        out = []
        for i, ((shape, dt), is_norm) in enumerate(zip(shapes, norms)):
            if is_norm:
                out.append(jnp.ones(shape, dt))
            else:
                k = jax.random.fold_in(key, i)
                out.append((init_std * jax.random.normal(k, shape, jnp.float32)).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make_weights(template, seed: int, init_std: float):
    """The weights of ``seed``, made on the device in one jitted call."""
    lo, hi = seed_words(seed)
    return jax.jit(weight_builder(template, init_std))(jnp.uint32(lo), jnp.uint32(hi))


def _as_stored(x):
    """``x`` (a fresh weight in its storage dtype) in float32, rounded as
    storing it rounds it. XLA may keep excess precision where a low-precision
    value is made and widened inside one program; ``reduce_precision`` pins
    the rounding that a stored copy has."""
    fi = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def change_norms(params, template, seed: int, init_std: float):
    """Per-leaf norms of ``params`` minus the seed's starting weights, with
    the start remade inside the call (no copy of it is kept)."""
    build = weight_builder(template, init_std)
    lo, hi = seed_words(seed)
    f = jax.jit(lambda p, lo, hi: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - _as_stored(b))))
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(build(lo, hi)))]))
    return f(params, jnp.uint32(lo), jnp.uint32(hi))
