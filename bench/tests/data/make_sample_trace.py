"""Record the small trace that tests/test_trace.py reads, on one TPU chip.

    python bench/tests/data/make_sample_trace.py

Three steps of a jitted matmul chain inside benchmark spans, with a host
sleep of 20 ms between steps so that the trace holds known idle gaps.
Writes bench/tests/data/sample.xplane.pb.
"""

import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(f(a))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(f(a))
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    import glob

    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    dst = os.path.join(HERE, "sample.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("wrote", dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
