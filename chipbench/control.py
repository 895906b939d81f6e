"""Readings of the control and of planted faults, for setting the limits.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed, the plain reference follows the cell's checked steps in
float32, and two stand-ins follow the same steps from the same weights and
batches in the program's place:

- ``int8``: the reference with every contraction in int8 (per-tensor
  absmax operands, and the cotangents quantized alike in the backward),
  the precision below the configuration's bf16 that would tempt a change;
- ``half_batch``: the reference on the first half of each batch's rows, the
  mean taken over them.

Each prints the numbers of ``check.train_numbers`` against the reference.
A third fault, a step that returns its state unchanged, reads 1 on
``update_norm_gap`` by construction and needs no run. The benchmark's own
runs never run this; ``tests/test_control.py`` runs it at a small size.
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

if __name__ == "__main__":
    # run as a script: the checkout's root, not this directory, leads the
    # import path
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from chipbench import check  # noqa: E402
from chipbench.drivers.train import (  # noqa: E402
    CHECKED_STEPS, reference_dither_s, seed_key)
from chipbench.tokens import TokenStream  # noqa: E402


def quantize(a: jax.Array) -> jax.Array:
    """Per-tensor absmax int8, returned as the values it stands for."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def int8_contract(exact):
    """``exact``'s contraction with int8 operands, forward and backward."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def contract(spec, a, b):
        return exact(spec, quantize(a), quantize(b))

    def fwd(spec, a, b):
        qa, qb = quantize(a), quantize(b)
        return exact(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        _, vjp = jax.vjp(lambda a, b: exact(spec, a, b), *res)
        return vjp(quantize(g))

    contract.defvjp(fwd, bwd)
    return contract


def readings(cell_config: dict, workload: dict, seed: int) -> dict:
    """{stand-in: numbers against the reference} for one seed."""
    fam = importlib.import_module(f"chipbench.models.{cell_config['family']}")
    ref = importlib.import_module(
        f"chipbench.reference.{cell_config['family']}")
    st = workload["stream"]
    stream = TokenStream(cell_config["vocab_size"], st["batch"],
                         st["seq_len"], seed, st["zipf_a"])
    batches = [{k: jnp.asarray(v) for k, v in stream(i).items()}
               for i in range(CHECKED_STEPS)]
    half = [{k: v[:v.shape[0] // 2] for k, v in b.items()} for b in batches]
    key = seed_key(seed)
    params = jax.jit(lambda k: fam.init(cell_config, k))(key)
    opt = workload["optimizer"]
    s = reference_dither_s(workload)

    def follow(batches, contract=ref.exact, salt=1):
        return ref.train_readings(params, batches, cell_config, opt,
                                  contract, s, jax.random.fold_in(key, salt))

    base = follow(batches)
    return {
        # the stand-ins draw their own dither noise, as the program does
        "int8": check.train_numbers(
            follow(batches, int8_contract(ref.exact), salt=2), base),
        "half_batch": check.train_numbers(follow(half, salt=3), base),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    spec = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = json.loads(
        (root / "chipbench" / "configs" / f"{spec['config']}.json").read_text())
    workload = json.loads(
        (root / "chipbench" / "workloads" / f"{args.workload}.json").read_text())
    from chipbench.run import enable_compile_cache, require_chips

    enable_compile_cache()
    require_chips(spec["chips"])
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(config, workload, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
