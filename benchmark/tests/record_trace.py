"""Record a small trace on the chip for ``test_reduce.py``: two small
jitted programs, one with a gap after it. Writes
``chiprun_out/small_trace/small.xplane.pb`` (copied by hand into this
directory as ``small.xplane.pb``)."""

import glob
import pathlib
import shutil
import time

import jax
import jax.numpy as jnp


@jax.jit
def bench_square(x):
    return x @ x


@jax.jit
def bench_scan(x):
    return jax.lax.scan(lambda c, _: (jnp.tanh(c @ c), None), x, None,
                        length=4)[0]


def main():
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    bench_square(x).block_until_ready()
    bench_scan(x).block_until_ready()
    out = pathlib.Path("chiprun_out/small_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out / "raw"), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench_host_span"):
            bench_square(x).block_until_ready()
            time.sleep(0.02)
        bench_scan(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(str(out / "raw/**/*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out / "small.xplane.pb")
    shutil.rmtree(out / "raw")
    print((out / "small.xplane.pb").stat().st_size, "bytes")


if __name__ == "__main__":
    main()
