"""Records ``chipbench/testdata/small.xplane.pb`` on one TPU chip.

    python3 chipbench/testdata/record.py

A small trace with what the reduction reads in a real run: the benchmark's
host spans (``window``, ``data``, ``dispatch``, ``sync``), a Pallas kernel
of the dithered backward (``nsd_quantize_blocked``) beside XLA ops, and a
known idle gap: each of the three steps sleeps 20 ms in ``data`` before it
is dispatched.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402

STEPS, SLEEP_S = 3, 0.02


@jax.jit
def step(g, key, w):
    q = ops.quantize_and_mask(g, key, 2.0)
    return q.k, jnp.tanh(g @ w).sum()


def main() -> None:
    assert jax.devices()[0].platform == "tpu", "records on a TPU only"
    g = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (1024, 1024), jnp.bfloat16)
    key = jax.random.PRNGKey(2)
    jax.block_until_ready(step(g, key, w))
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(STEPS):
                with jax.profiler.TraceAnnotation("data"):
                    time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("dispatch"):
                    out = step(g, key, w)
                with jax.profiler.TraceAnnotation("sync"):
                    jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (pb,) = Path(tmp).rglob("*.xplane.pb")
        shutil.copy(pb, Path(__file__).with_name("small.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
