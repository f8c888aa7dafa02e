"""Record ``cpu_trace.xplane.pb``, the small CPU trace that
``test_bench_trace.py`` reduces.

    JAX_PLATFORMS=cpu python bench/testdata/make_cpu_trace.py

Inside a ``bench.window`` span: three rounds of a jitted op inside
``bench.submit``, each followed by a 30 ms sleep inside ``bench.sample``
(the longest idle gaps), then a 10 ms sleep outside any bench span."""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "cpu_trace.xplane.pb")


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.submit"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.sample"):
                    time.sleep(0.03)
            time.sleep(0.01)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)[0]
        shutil.copy(src, OUT)


if __name__ == "__main__":
    main()
