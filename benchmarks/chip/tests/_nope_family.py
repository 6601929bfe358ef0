"""A family for tests only: yi-6b's dense decoder with no positional
encoding (NoPE), which the dense family's fields refuse (``use_rope``).

It stands for what a later configuration brings: a module of its own that
the harness, the check and the metric readers reach by the config file's
``reference`` key.  Its output head starts from a function of its own, a
uniform draw on [-1, 1], where the dense family draws a normal one.
Rotary costs only elementwise work, which the counts leave out, so they are
the dense decoder's.
"""
import jax
import jax.numpy as jnp

from benchmarks.chip.families import dense_decoder as dense

sizes, view, tiny = dense.sizes, dense.view, dense.tiny
prefill_flops, decode_flops, sequence_flops = (
    dense.prefill_flops, dense.decode_flops, dense.sequence_flops)
step_bytes, token_bytes, prefill_bytes = dense.step_bytes, dense.token_bytes, dense.prefill_bytes


def program_fields(config, s):
    return dict(dense.program_fields(config, s), use_rope=False)


def uniform_head(key, shape, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0).astype(dtype)


def init_kind(keys, cfg):
    if keys[-1] == "lm_head":
        return uniform_head
    return dense.init_kind(keys, cfg)
