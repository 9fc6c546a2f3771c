#!/usr/bin/env python3
"""Record the small profiler trace that ``bench/tests`` reads.

Runs a few tiny jitted programs inside the benchmark's window span, with
two host spans inside it and idle time between, under the JAX profiler,
and copies the ``.xplane.pb`` to ``--out``.  Run it on the chip.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.fixture.device"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.fixture.host"):
                    time.sleep(0.02)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copy(path, args.out)
        print(f"wrote {args.out}: {os.path.getsize(args.out)} bytes")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
