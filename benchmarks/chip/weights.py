"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's own
``init_params`` is used only through ``jax.eval_shape``, for the layout its
serving steps expect.  Every matrix is drawn from N(0, initializer_range**2)
of the config file, in float32 and then cast to the served type; every norm
starts at the published identity (scale 1, bias 0).  The reference reads the
same matrices through :func:`layer_view` and takes its norms from here, not
from the program's arrays.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln1", "ln2", "final_norm")


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from any whole-number seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def _kind(path, norm: str) -> str:
    keys = [getattr(k, "key", None) for k in path]
    if any(k in NORMS for k in keys):
        # the program keeps an RMSNorm weight as an offset from 1
        if keys[-1] == "bias" or (norm == "rmsnorm" and keys[-1] == "scale"):
            return "zeros"
        return "ones"
    return "normal"


def make_params(cfg, std: float, seed: int):
    """The program's parameter tree for ``cfg``, drawn from ``seed``."""
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = [_kind(p, cfg.norm) for p, _ in flat]
    specs = [s for _, s in flat]

    def gen(key):
        out = []
        for i, (s, kind) in enumerate(zip(specs, kinds)):
            if kind == "zeros":
                out.append(jnp.zeros(s.shape, s.dtype))
            elif kind == "ones":
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
                out.append((x * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(seed32(seed), impl="rbg")
    return jax.block_until_ready(jax.jit(gen)(key))


def layer_view(params) -> List[Dict[str, jax.Array]]:
    """Per layer, the matrices under the reference's names, ``x @ W`` form:
    ``wq (d, H*hd)``, ``wk``/``wv (d, K*hd)``, ``wo (H*hd, d)``,
    ``w_gate``/``w_up (d, d_ff)``, ``w_down (d_ff, d)``."""
    out = []
    for lp in params["layers"]:
        a, f = lp["attn"], lp["ffn"]
        out.append({"wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
                    "w_gate": f["wg"], "w_up": f["wi"], "w_down": f["wo"]})
    return out


def embedding(params) -> jax.Array:
    return params["embed"]


def output_head(params) -> jax.Array:
    """(d, V): the untied head, or the embedding's transpose when tied."""
    return params["lm_head"] if "lm_head" in params else params["embed"].T
