"""Record the small device trace that ``tests/bench`` reads.

    python bench/testdata/record_small_trace.py <out_dir>

On a TPU: three units of a small jitted loop inside the benchmark's
``bench.window`` / ``bench.unit`` spans, with a host-only
``bench.host`` span between units so the trace holds named idle gaps.
Copy the resulting ``.xplane.pb`` to ``bench/testdata/small.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir):
    step = jax.jit(lambda x: jax.lax.fori_loop(
        0, 200, lambda i, v: jnp.sin(v) * 1.0001 + v[::-1], x))
    x = jnp.ones((256, 128), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.unit"):
                x = step(x)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host"):
                time.sleep(0.002)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
