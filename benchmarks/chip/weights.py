"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's own
``init_params`` is used only through ``jax.eval_shape``, for the layout its
serving steps expect.  How each leaf starts is the family's to say
(``init_kind``): drawn from N(0, initializer_range**2) of the config file, in
float32 and then cast to the served type; zeros; ones; or a function of its
own.  The reference reads the same arrays through the family's ``view``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from any whole-number seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def make_params(cfg, std: float, seed: int, init_kind: Callable):
    """The program's parameter tree for ``cfg``, drawn from ``seed``.

    ``init_kind(keys, cfg)`` gives, for the leaf at the dict keys ``keys``
    (None for a list index), ``"normal"``, ``"zeros"``, ``"ones"`` or a
    function ``(key, shape, dtype) -> array``.  Leaf ``i`` in flatten order
    draws from ``fold_in(key, i)``.
    """
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = [init_kind([getattr(k, "key", None) for k in p], cfg) for p, _ in flat]
    specs = [s for _, s in flat]

    def gen(key):
        out = []
        for i, (s, kind) in enumerate(zip(specs, kinds)):
            if kind == "zeros":
                out.append(jnp.zeros(s.shape, s.dtype))
            elif kind == "ones":
                out.append(jnp.ones(s.shape, s.dtype))
            elif kind == "normal":
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
                out.append((x * std).astype(s.dtype))
            else:
                out.append(kind(jax.random.fold_in(key, i), s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(seed32(seed), impl="rbg")
    return jax.block_until_ready(jax.jit(gen)(key))
