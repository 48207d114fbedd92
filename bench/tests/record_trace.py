"""Record the small CPU trace that ``test_bench_trace_reduce.py`` reads.

    JAX_PLATFORMS=cpu python bench/tests/record_trace.py

Inside one ``bench.window`` span: three ``bench.step`` spans that each run
a jitted matrix product to completion, each followed by a 20 ms
``bench.idle`` span in which nothing runs.  A CPU trace: its numbers test
the reduction's arithmetic and are no device numbers.
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def main():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
    shutil.copy(src, OUT)
    shutil.rmtree(tmp)
    print(OUT, OUT.stat().st_size)


if __name__ == "__main__":
    main()
