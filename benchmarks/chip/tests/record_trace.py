"""Record the small trace ``testdata/small_trace.xplane.pb`` on a TPU.

    python3 benchmarks/chip/tests/record_trace.py

Two jitted programs named as the serving steps are, ``prefill_step`` run
twice and ``serve_step`` five times, each run to completion before the next,
with host sleeps between them; the test of the trace reduction checks the
counts and the idle gaps against this schedule.  The checkout's absolute
path, which the trace carries in its source locations, is masked with ``#``.
"""
import glob
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


@jax.jit
def prefill_step(x):
    return jnp.tanh(x @ x) @ x


@jax.jit
def serve_step(x):
    return (x @ x[:, :128]).sum(0)


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((prefill_step(x), serve_step(x)))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for _ in range(2):
        jax.block_until_ready(prefill_step(x))
        time.sleep(0.02)
    for _ in range(5):
        jax.block_until_ready(serve_step(x))
        time.sleep(0.01)
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(HERE, "..", "testdata", "small_trace.xplane.pb")
    root = os.path.dirname(os.path.dirname(os.path.dirname(HERE))).encode()
    with open(src, "rb") as f:
        data = f.read().replace(root, b"#" * len(root))  # same length keeps it valid
    with open(dst, "wb") as f:
        f.write(data)
    print(f"{os.path.getsize(dst)} bytes -> {dst}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
