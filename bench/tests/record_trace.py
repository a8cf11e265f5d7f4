"""Record the small profiler trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Needs the chip.  Inside a ``bench.window`` span, two ``bench.refresh`` spans
each run a jitted matrix product on the device and then sleep 20 ms on the
host, so the trace holds device ops, host spans and idle gaps with known
labels.  The ``.xplane.pb`` is copied to the path given.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    mm = jax.jit(lambda a: a @ a)
    x = jnp.ones((2048, 2048), jnp.float32)
    mm(x).block_until_ready()                   # compiled before the trace
    tdir = tempfile.mkdtemp(prefix="record_trace_")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.refresh"):
                mm(x).block_until_ready()
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, argv[0])
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"{argv[0]}: {os.path.getsize(argv[0])} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
