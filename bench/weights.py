"""Seeded random weights for a served model, made on the device in one call.

The program declares its parameter tree (``model.specs()``: paths, shapes,
storage dtypes, and a ``circulant`` tag on block-circulant tables); the
benchmark fills it. Each leaf is drawn from a normal distribution whose
scale depends on what the leaf is:

* projections (dense ``(in, out)`` or circulant ``(p, q, k)``):
  std ``1 / sqrt(fan_in)``, fan_in = ``in`` or ``q * k``, so each output
  keeps the variance of its input;
* the embedding table: std ``EMBED_STD / sqrt(d)`` (see below);
* norm scales (used as ``1 + scale``): std 0.1, so the reference has to
  apply them.

The program's own initializer gives the embedding std 1. With a tied head
an untrained model then repeats its input token at a logit margin of about
``d``, which no rounding can flip, and a check on served tokens could not
tell a lower precision from the configured one. With a tied head the input
token's logit stands ``d * std / R`` standard deviations above the others
(``R``: the residual stream's scale at the last layer, about the square
root of twice the depth); at std ``0.25 / sqrt(d)`` that is about one, so
the context decides the next token and near-ties occur, as in a trained
model. The logits' scale is then about 0.25.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

__all__ = ["leaves", "leaf_std", "make_weights", "NORM_STD", "EMBED_STD"]

NORM_STD = 0.1
EMBED_STD = 0.25


def leaves(specs, path=()) -> List[Tuple[tuple, Any]]:
    """``(path, spec)`` for every leaf of a nested-dict spec tree, in
    sorted key order."""
    if isinstance(specs, dict):
        out = []
        for k in sorted(specs):
            out.extend(leaves(specs[k], path + (k,)))
        return out
    return [(path, specs)]


def leaf_std(path: tuple, shape: tuple, tags: tuple) -> float:
    if path[-1] == "scale":
        return NORM_STD
    if path[0] == "embed":
        return EMBED_STD * shape[-1] ** -0.5
    if "circulant" in tags:
        return (shape[-2] * shape[-1]) ** -0.5
    return shape[-2] ** -0.5


def _key(seed: int) -> jax.Array:
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(specs, seed: int) -> Dict[str, Any]:
    """The weight tree for ``specs``, drawn from ``seed``, in each leaf's
    storage dtype, made by one jitted call on the default device."""
    flat = [(p, tuple(s.shape), jnp.dtype(s.dtype), tuple(s.tags))
            for p, s in leaves(specs)]

    def draw(key):
        tree: Dict[str, Any] = {}
        for i, (path, shape, dtype, tags) in enumerate(flat):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * leaf_std(path, shape, tags)
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = x.astype(dtype)
        return tree

    return jax.jit(draw)(_key(seed))
