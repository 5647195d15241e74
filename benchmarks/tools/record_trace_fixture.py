"""Record the small device trace that tests/benchmarks checks
``trace_reduce`` against (run once on the chip; the result is committed as
tests/benchmarks/fixtures/tpu_small.xplane.pb):

    python3 benchmarks/tools/record_trace_fixture.py <out_dir>

Three dispatches of a small jitted program with a loop inside (so the trace
holds nested device events), each under a ``bench:dispatch`` span, with a host
sleep between them under ``bench:pause``.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    @jax.jit
    def f(x):
        def body(i, a):
            return jnp.tanh(a @ a) * 0.5
        return jax.lax.fori_loop(0, 8, body, x).sum()

    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace_tmp")
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:dispatch"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:pause"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "tpu_small.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
