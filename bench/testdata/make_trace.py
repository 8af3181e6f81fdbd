"""Records the small profiler trace the trace-reduction test reads.

    python3 bench/testdata/make_trace.py   # on a machine with the chip

A few jitted sorts, each inside a `query` annotation, spaced by idle
sleeps, all inside the `window` annotation the harness also writes;
writes bench/testdata/small.xplane.pb and prints what it recorded.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    f = jax.jit(lambda x: jnp.sort(x * 3 + 1))
    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("query"):
                f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True))[-1]
    dst = os.path.join(HERE, "small.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(d, ignore_errors=True)
    print(f"{dst}: {os.path.getsize(dst)} bytes, device "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main()
