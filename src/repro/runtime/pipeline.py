"""GPipe-style pipeline over the ``pod`` axis (optional multi-pod layout).

The baseline multi-pod config treats ``pod`` as outer data parallelism;
this module provides the alternative: layers split into ``n_stages``
contiguous groups, microbatches stream through stages via
``jax.lax.ppermute`` under ``shard_map``.  Activations cross pods as HGum
frames conceptually — here the activation block itself is the frame payload
(fixed (mb, S, d) size, so a single-frame list; headers would be constant
and are elided in the math but accounted in the channel benchmarks).

Used at small scale in tests (2 stages on 2 fake devices) and selectable in
the dry-run via ``--pipeline``.
"""
from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def split_stages(layers: List, n_stages: int) -> List[List]:
    """Contiguous split of the layer list into n_stages groups."""
    n = len(layers)
    per = -(-n // n_stages)
    return [layers[i * per : (i + 1) * per] for i in range(n_stages)]


def stack_stage_params(stage_groups: List[List]) -> PyTree:
    """Stack per-stage param groups on a leading stage axis (must be
    homogeneous across stages — enforced by the caller's layer plan)."""
    stage_trees = [
        jax.tree.map(lambda *xs: jnp.stack(xs), *grp) for grp in stage_groups
    ]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_trees)


def gpipe_forward(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    stage_params: PyTree,  # leaves (n_stages, layers_per_stage, ...)
    x: jnp.ndarray,  # (n_micro, mb, S, d) microbatched activations
) -> jnp.ndarray:
    """Forward-only GPipe schedule: n_micro + n_stages - 1 ticks.

    stage_fn(params_for_stage, acts) -> acts.  Stage s processes microbatch
    m at tick t = s + m; between ticks activations rotate one hop.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def per_stage(params, xs):  # runs under shard_map; xs: (1, n_micro, mb,S,d)
        params = jax.tree.map(lambda p: p[0], params)
        xs = xs[0]
        sid = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs)  # outputs per microbatch (only stage!=0 uses)
        carry = jnp.zeros_like(xs[0])

        def tick(t, state):
            carry, buf = state
            m_in = t - sid  # microbatch arriving at this stage this tick
            valid = (m_in >= 0) & (m_in < n_micro)
            # stage 0 reads its own input; others read the rotated carry
            mb_idx = jnp.clip(m_in, 0, n_micro - 1)
            x_own = jax.lax.dynamic_index_in_dim(xs, mb_idx, keepdims=False)
            x_in = jnp.where(sid == 0, x_own, carry)
            y = stage_fn(params, x_in)
            y = jnp.where(valid, y, 0)
            # last stage stores its outputs
            buf = jnp.where(
                (sid == n_stages - 1) & valid,
                jax.lax.dynamic_update_index_in_dim(buf, y, mb_idx, 0),
                buf,
            )
            # rotate activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            carry = jax.lax.ppermute(y, axis, perm)
            return carry, buf

        carry, buf = jax.lax.fori_loop(0, n_ticks, tick, (carry, buf))
        # only the last stage's buffer holds real outputs (caller slices)
        return buf[None]

    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(axis),
        check_vma=False,
    )
    out = fn(stage_params, x[None])
    # row s of `out` is stage s's buffer; the final outputs live in the last
    # stage's row.
    return out[-1]
