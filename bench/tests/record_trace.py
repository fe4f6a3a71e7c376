"""Records the small TPU trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py <out_dir>

On one TPU chip: three runs of a small jitted matmul, 50 ms of host
sleep between them, inside one ``bench.query`` annotation.  Copy the
``.xplane.pb`` it writes under <out_dir> to ``bench/tests/data/``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.query"):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
