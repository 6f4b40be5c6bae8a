"""Record the small device trace kept in `benchmark/tests/data/`.

    python3 benchmark/tests/record_trace.py <out_dir>     (on a TPU host)

Three executions of one small jitted program, each under the benchmark's
`bench.train` span with a host sleep between them, all under `bench.slice`:
a trace with known structure (three busy stretches, idle gaps the host
spent asleep) for `test_reduction.py`.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.slice"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train"):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host.sleep"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    print(jax.devices()[0].device_kind, out_dir)


if __name__ == "__main__":
    main(sys.argv[1])
