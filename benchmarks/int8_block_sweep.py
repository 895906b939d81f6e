"""Chip sweep of the int8 tile-skipping matmul's compute-block caps.

Times ``bsp_matmul_int8``'s Pallas call at mamba2-370m's four backward
products per layer (8192 tokens; in-proj width 4384 padded to 4480) with
every tile live, as the training step runs them, at several cap sets, and
XLA's int8 dot (``lax.dot_general`` int8 -> int32, rescaled to f32) as the
ceiling. Before timing, each block set's output is checked bit-identical to
the 128 x 128 x 128 grid's, and a mixed mask (live, partly live and dead
blocks) against ``bsp_matmul_int8_ref``.

    PYTHONPATH=src python -m benchmarks.int8_block_sweep \\
        [--out benchmarks/results/int8_block_sweep.json]

Needs a TPU. Prints one line per (shape, block set) and writes the table as
JSON. The caps the kernel uses (``_INT8_BLOCK_CAPS``) come from this table.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsp_matmul import bsp_matmul as bsp
from repro.kernels.bsp_matmul.ref import bsp_matmul_int8_ref

TILE = 128
# (name, M, K, N): in-proj dx = g~ @ w^T, dW^T = g~^T @ x; out-proj alike
SHAPES = [("in_dx", 8192, 4480, 1024), ("in_dw", 4480, 8192, 1024),
          ("out_dx", 8192, 1024, 2048), ("out_dw", 1024, 8192, 2048)]
CAPS = [(128, 128, 128), (512, 512, 512), (1024, 1024, 1024),
        (1024, 2048, 1024), (2048, 1024, 1024), (1024, 1024, 2048),
        (2048, 2048, 1024)]
SWEEP_BUDGET = 48 * 2**20  # wide enough that the caps alone decide


def _median_s(fn, args, iters=20, reps=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    return statistics.median(times)


def _kernel(blocks):
    return jax.jit(functools.partial(bsp._int8_call, tile=TILE, blocks=blocks,
                                     out_dtype=jnp.float32, interpret=False))


@jax.jit
def _xla_int8(a, b, scale, mask):
    del mask
    acc = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * scale


def _operands(key, M, K, N):
    ka, kb = jax.random.split(key)
    a = jax.random.randint(ka, (M, K), -8, 9, jnp.int32).astype(jnp.int8)
    b = jax.random.randint(kb, (K, N), -127, 128, jnp.int32).astype(jnp.int8)
    return a, b, jnp.float32(1.7e-3)


def check_mixed_mask(key):
    """A 2 x 2 grid of 640 x 896 blocks: partly live, dead, fully live."""
    M, K, N = 1280, 1792, 384
    a, b, scale = _operands(key, M, K, N)
    mask = np.asarray(jax.random.bernoulli(key, 0.5, (M // TILE, K // TILE)),
                      np.int32)
    mask[:5, 7:] = 0  # block (0, 1) dead
    mask[5:, 7:] = 1  # block (1, 1) fully live
    mask = jnp.asarray(mask)
    out = bsp.bsp_matmul_int8(a, b, scale, mask, interpret=False)
    ref = bsp_matmul_int8_ref(a, b, scale, mask)
    return bool(np.array_equal(np.asarray(out), np.asarray(ref)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out",
                    default="benchmarks/results/int8_block_sweep.json")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"int8_block_sweep needs a TPU, found {dev}")
    peaks = json.loads((Path(__file__).resolve().parents[1] / "chipbench"
                        / "peaks.json").read_text())
    peak = peaks[dev.device_kind]["int8_ops"]
    key = jax.random.PRNGKey(20261018)
    mixed_ok = check_mixed_mask(key)
    print(f"mixed mask bit-identical to the reference: {mixed_ok}",
          flush=True)
    rows = []
    for name, M, K, N in SHAPES:
        a, b, scale = _operands(jax.random.fold_in(key, M + K + N), M, K, N)
        mask = jnp.ones((M // TILE, K // TILE), jnp.int32)
        base = np.asarray(_kernel((TILE,) * 3)(a, b, scale, mask))
        ops = 2 * M * K * N
        variants = []
        for c in CAPS:
            blocks = bsp._int8_blocks(M, K, N, TILE, 4, caps=c,
                                      budget=SWEEP_BUDGET)
            variants.append((f"caps {c[0]}/{c[1]}/{c[2]}", _kernel(blocks),
                             blocks))
        variants.append(("xla int8 dot", _xla_int8, None))
        for label, fn, blocks in variants:
            out = np.asarray(fn(a, b, scale, mask))
            s = _median_s(fn, (a, b, scale, mask))
            steps = (None if blocks is None else
                     (M // blocks[0]) * (K // blocks[1]) * (N // blocks[2]))
            row = {"shape": name, "mkn": [M, K, N], "variant": label,
                   "blocks": blocks, "grid_steps": steps, "ms": 1e3 * s,
                   "int8_peak_pct": 100.0 * ops / s / peak,
                   "equal_to_128": bool(np.array_equal(out, base))}
            rows.append(row)
            print(json.dumps(row), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"device": {"platform": dev.platform, "device_kind": dev.device_kind,
                    "count": jax.device_count()},
         "mixed_mask_equal_to_ref": mixed_ok, "rows": rows}, indent=1))
    ok = mixed_ok and all(r["equal_to_128"] for r in rows
                          if r["blocks"] is not None)
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
