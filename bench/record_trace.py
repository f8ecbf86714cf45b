#!/usr/bin/env python3
"""Record a small profiler trace of the kind the benchmark reads, for the
test of the trace reduction (``tests/data/tpu_trace.xplane.pb``).

    python3 bench/record_trace.py OUT_DIR

On the device JAX finds, runs a few steps of two jitted programs inside
host annotations named as the benchmark names its calls
(``bench:decode#<i>``, ``bench:insert#<i>``), with host-side work between
them, and one call after the window (``bench_after:decode#<i>``), under
the profiler. Writes the ``.xplane.pb`` under OUT_DIR and prints its path.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    @jax.jit
    def small(x):
        return jnp.tanh(x @ x) + 1.0

    @jax.jit
    def large(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready((small(x), large(x)))      # compile outside
    jax.profiler.start_trace(out)
    i = 0
    for step in range(6):
        kind, fn = ("insert", large) if step % 3 == 0 else ("decode", small)
        with jax.profiler.TraceAnnotation(f"bench:{kind}#{i}"):
            jax.block_until_ready(fn(x))
        i += 1
        time.sleep(0.002)                            # the host's own work
    with jax.profiler.TraceAnnotation(f"bench_after:decode#{i}"):
        jax.block_until_ready(small(x))
    jax.profiler.stop_trace()
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from harness import trace
    print(trace.find(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
