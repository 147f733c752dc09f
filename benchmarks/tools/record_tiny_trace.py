#!/usr/bin/env python3
"""Record a small ``.xplane.pb`` on the chip for ``tests/test_trace.py``:
a few runs of a small jitted program (matmuls inside a while loop, so ops
nest) with the harness's anchor annotation and two gaps the host sleeps
through. Writes ``chiprun_out/tiny_tpu.xplane.pb`` (copy it to
``benchmarks/tests/data/``).
"""

from __future__ import annotations

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import common, trace  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp

    common.require_chips(1)

    @jax.jit
    def tiny_step_fn(x, w):
        def body(_, x):
            return jnp.tanh(x @ w)
        return jax.lax.fori_loop(0, 4, body, x)

    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.full((256, 256), 0.01, jnp.bfloat16)
    tiny_step_fn(x, w).block_until_ready()
    tw = trace.TraceWindow(os.path.join(common.REPO, ".bench_out", "tiny"))
    tw.start()
    for _ in range(3):
        x = tiny_step_fn(x, w)
        x.block_until_ready()
        time.sleep(0.002)
    tw.stop()
    out = os.path.join(common.REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    path = trace.find_xplane(tw.dir)
    shutil.copy(path, os.path.join(out, "tiny_tpu.xplane.pb"))
    red = tw.reduce([("sleep", tw.t_start, tw.t_stop, 0)])
    print({k: v for k, v in red.items() if k != "trace"},
          os.path.getsize(os.path.join(out, "tiny_tpu.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
