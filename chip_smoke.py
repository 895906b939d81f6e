"""Smoke run of dithered-backprop training on TPU chips, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the compressed ring reduce

On one chip it runs two phases, and any failure exits non-zero:

1. Kernel parity at real width. One mamba2-370m ``L.ssm.in`` projection
   (4096 tokens x 1024 -> 4384; 4384 is not a multiple of 128, so the
   padding path runs) is differentiated under ``variant=kernel`` (fused
   NSD + bitmap pack + tile-skipping int8 matmuls, compiled through
   Mosaic) and under ``variant=paper`` with the same key. The quantized
   ``k`` must be bit-identical; ``dx`` and ``dW`` must agree within
   ``PARITY_RTOL``, which covers the kernel path's absmax-int8 operands.
2. Training. ``repro.launch.train.run`` trains mamba2-370m at its published
   widths (``--preset full``) with the base ``--dither paper`` and
   ``L.ssm.*`` on ``variant=kernel``. The loss must be finite at every step
   and lower at the last step than at the first, no kernel may have fallen
   back (``KERNEL_FALLBACKS``), and the compiled step must hold each kernel
   of the layer as a ``tpu_custom_call``.

With ``--chips 4`` it runs only the data-parallel path: one SSGD step of
mamba2-370m whose node gradients cross a 4-chip mesh through the
compressed ring all-reduce, and the same ring reduce of one set of node
gradients against their exact mean. The reduced gradient must lie within
the reducer's own ``error_bound`` of the exact mean, and the node axis
must lie over 4 distinct devices.

Lines before the last are for the record: timings, memory and versions
printed here are smoke output, not benchmark metrics. The last line is one
JSON object, ``{"ok": true, "device": {...}}``. Without a TPU the script
exits non-zero before any phase and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# one L.ssm.in projection of mamba2-370m
TOKENS, D_MODEL, D_IN_PROJ = 4096, 1024, 4384
S = 2.0  # the paper's default Delta = s * std
# relative Frobenius error of the kernel path's dx/dW against the paper
# path: the kernel multiplies absmax-int8 x and w (about 1% relative error
# each for Gaussian operands); the paper path multiplies in bf16
PARITY_RTOL = 0.03

# training run: 4 x 2048 = 8192 tokens per step (the compiled step's
# memory analysis leaves about 5 GB of the 16 GB chip free)
TRAIN_BATCH, TRAIN_SEQ = 4, 2048
WARMUP_STEPS, TIMED_STEPS = 2, 8
TRAIN_LR = 1.5e-3
TRAIN_PROGRAM = "dither: rule L.ssm.*:variant=kernel"
# kernels the kernel-variant backward of a dense layer runs
LAYER_KERNELS = ("nsd_quantize_blocked", "bitmap_pack_blocked",
                 "bsp_matmul_int8")

# four-chip SSGD step: one sequence of 2048 tokens per node, depth cut to
# 2 layers (published widths). At 48 layers the step's replicated params,
# AdamW state and outputs compile to 14.5 of the 15.75 GB per chip
RING_NODES, RING_SEQ, RING_LAYERS = 4, 2048, 2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, JAX found {len(devices)}")
    return devices


def rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def kernel_parity() -> None:
    """variant=kernel against variant=paper on one L.ssm.in projection."""
    import jax
    import jax.numpy as jnp

    from repro.core import DitherCtx, DitherPolicy, dense, nsd
    from repro.kernels import ops

    name = "L.ssm.in"
    kx, kw, kg, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(kx, (TOKENS, D_MODEL), jnp.float32)
    w = jax.random.normal(kw, (D_MODEL, D_IN_PROJ)) / math.sqrt(D_MODEL)
    g = jax.random.normal(kg, (TOKENS, D_IN_PROJ)) * 1e-3
    x, w, g = (a.astype(jnp.bfloat16) for a in (x, w, g))

    def backward(variant, x, w, g, key):
        ctx = DitherCtx.for_step(key, 0, DitherPolicy(variant=variant, s=S))
        _, vjp = jax.vjp(lambda x, w: dense(x, w, ctx=ctx, name=name), x, w)
        return vjp(g)

    dx_k, dw_k = jax.jit(lambda *a: backward("kernel", *a))(x, w, g, kd)
    dx_p, dw_p = jax.jit(lambda *a: backward("paper", *a))(x, w, g, kd)

    # the layer's key, as DitherCtx resolves it for both variants
    lkey = DitherCtx.for_step(kd, 0, DitherPolicy(s=S)).key_for(name)
    q = jax.jit(lambda g, k: ops.quantize_and_mask(g, k, S))(g, lkey)
    k_ref = jax.jit(lambda g, k: nsd.nsd_indices(
        g, k, nsd.compute_delta(g, S)))(g, lkey)
    k_kernel = q.k[:TOKENS, :D_IN_PROJ].astype(jnp.int32)
    mismatches = int(jnp.sum(k_kernel != k_ref))
    err_dx, err_dw = rel_err(dx_k, dx_p), rel_err(dw_k, dw_p)
    say(f"parity {name} ({TOKENS}x{D_MODEL} -> {D_IN_PROJ}): "
        f"k mismatches {mismatches} of {k_ref.size}, "
        f"nonzero {float(jnp.mean(k_ref != 0)):.4f}, "
        f"dx rel err {err_dx:.5f}, dW rel err {err_dw:.5f} "
        f"(limit {PARITY_RTOL})")
    check(mismatches == 0, "kernel k differs from the paper path's")
    check(bool(jnp.all(jnp.isfinite(dx_k)) & jnp.all(jnp.isfinite(dw_k))),
          "non-finite kernel gradients")
    check(err_dx < PARITY_RTOL and err_dw < PARITY_RTOL,
          f"dx/dW parity {err_dx}, {err_dw}")


def kernels_in_hlo(hlo: str) -> dict:
    """Count of ``tpu_custom_call`` instructions per layer kernel."""
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return {k: sum(f"jit({k})/" in line for line in calls)
            for k in LAYER_KERNELS}


def train_run():
    """mamba2-370m through the training launcher; returns (out, hlo)."""
    import jax

    from repro.kernels.ops import KERNEL_FALLBACKS
    from repro.launch import train

    batch, seq, steps = TRAIN_BATCH, TRAIN_SEQ, WARMUP_STEPS + TIMED_STEPS
    argv = ["--arch", "mamba2-370m", "--preset", "full",
            "--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
            "--dither", "paper", "--program", TRAIN_PROGRAM,
            "--lr", str(TRAIN_LR)]
    say(f"train: python -m repro.launch.train {' '.join(argv)!s}")
    say(f"train: {batch} x {seq} = {batch * seq} tokens per step")
    args = train.build_parser().parse_args(argv)
    out = train.run(args)
    hist = out["history"]
    losses = [row["loss"] for row in hist]
    say("train: loss per step " + " ".join(f"{v:.4f}" for v in losses))
    check(len(hist) == steps, f"{len(hist)} logged steps of {steps}")
    check(all(math.isfinite(v) for v in losses), "non-finite loss")
    check(losses[-1] < losses[0], "loss did not fall")
    check(not KERNEL_FALLBACKS, f"kernel fallbacks {KERNEL_FALLBACKS}")

    # each history row is stamped once the step's loss is on the host,
    # which waits for the whole step program
    first_s = hist[0]["time_s"]
    steady_s = ((hist[-1]["time_s"] - hist[WARMUP_STEPS - 1]["time_s"])
                / (steps - WARMUP_STEPS))
    say(f"train: first step {first_s} s (compile included), steady step "
        f"{steady_s} s over steps {WARMUP_STEPS + 1}..{steps}, compile about "
        f"{first_s - steady_s} s")
    # the step as fit() compiled it (the lowering hits jit's cache)
    trainer = out["trainer"]
    batch0 = train.batch_fn_for(trainer.model, batch, seq)(0)
    compiled = trainer.lower_step(out["params"], out["opt_state"],
                                  batch0).compile()
    ma = compiled.memory_analysis()
    if ma is not None:
        say(f"train: compiled step memory: arguments "
            f"{ma.argument_size_in_bytes} B, outputs "
            f"{ma.output_size_in_bytes} B, aliased "
            f"{ma.alias_size_in_bytes} B, temp {ma.temp_size_in_bytes} B")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return out, compiled.as_text()


def ring_reduce():
    """One SSGD step over a 4-chip node mesh: compressed ring vs exact."""
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm import CommPolicy, reducer
    from repro.configs import get_model
    from repro.core import DitherCtx, DitherPolicy
    from repro.data import TokenStreamConfig, token_batch
    from repro.distributed import SSGDConfig, make_ssgd_step, shard_batch
    from repro.launch import make_mesh
    from repro.models.api import ssm_model
    from repro.optim import OptConfig, init_opt_state

    n, seq = RING_NODES, RING_SEQ
    model = ssm_model(dataclasses.replace(get_model("mamba2-370m").cfg,
                                          n_layers=RING_LAYERS))
    say(f"ring: mamba2-370m widths, {model.cfg.n_layers} layers, {n} nodes "
        f"x {seq} tokens")
    mesh = make_mesh((n,), ("nodes",))
    key = jax.random.PRNGKey(0)
    params, _ = model.init(key)
    batch = shard_batch(token_batch(
        TokenStreamConfig(vocab=model.cfg.vocab, seq_len=seq, batch=n), 0), n)
    batch = jax.device_put(batch, NamedSharding(mesh, P("nodes")))
    shards = batch["tokens"].addressable_shards
    devices = {s.device for s in shards}
    say(f"ring: node axis over devices "
        f"{sorted(d.id for d in devices)}, shard shapes "
        f"{[s.data.shape for s in shards]}")
    check(len(shards) == n and len(devices) == n,
          f"node axis not over {n} devices")

    # b1 = 0 and no clipping: after one step mu holds the reduced gradient
    opt = OptConfig(lr=0.0, b1=0.0, grad_clip=None)
    dcfg = SSGDConfig(n_nodes=n, s_schedule="fixed", s_base=S)
    policy = DitherPolicy(variant="paper", s=S)
    comm = CommPolicy(topology="ring")
    ring_step, _ = make_ssgd_step(model, opt, dcfg, policy, comm, mesh=mesh)
    state = init_opt_state(params, opt)

    # The reference mean must see the very node gradients the ring reduced.
    # A second, separately compiled step does not: the dithered backward is
    # not bit-reproducible across two programs (a one-ulp change in a
    # cotangent moves its level by a whole Delta), so the node gradients
    # are computed once here and fed to both the ring and the exact mean.
    nodes = NamedSharding(mesh, P("nodes"))

    def one_node(params, node_batch, worker):
        ctx = DitherCtx.for_step(key, 0, policy, worker=worker)
        return jax.grad(lambda p: model.loss(p, node_batch, ctx=ctx))(params)

    node_grads = jax.jit(
        lambda p, b: jax.vmap(one_node, in_axes=(None, 0, 0))(
            p, b, jnp.arange(n)),
        out_shardings=nodes)
    red = reducer(comm, mesh, n_nodes=n, stacked=True)

    def reduce_both(grads):
        ring, tele, _ = red.reduce(grads, key, 0, None)
        exact = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), 0),
                             grads)
        return ring, exact, tele

    grads_shape = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=nodes),
        jax.eval_shape(node_grads, params, batch))
    # the two ring programs compile side by side (XLA frees the GIL)
    t0 = time.perf_counter()
    lowered = [ring_step.lower(params, state, batch, key),
               node_grads.lower(params, batch),
               jax.jit(reduce_both).lower(grads_shape)]
    with ThreadPoolExecutor(len(lowered)) as pool:
        step_c, grads_c, reduce_c = pool.map(lambda lo: lo.compile(),
                                             lowered)
    say(f"ring: ring step, node gradients and reduce compiled in "
        f"{time.perf_counter() - t0:.3f} s")
    # the ring's hops are device-to-device permutes in the program
    permutes = step_c.as_text().count("collective-permute")
    say(f"ring: collective-permute ops in the ring step: {permutes}")
    check(permutes > 0, "the ring step moves nothing between devices")

    # 1. the SSGD step through the ring, as a user runs it
    t0 = time.perf_counter()
    new_params, st, metrics, _ = step_c(params, state, batch, key)
    jax.block_until_ready(st)
    loss = float(metrics["loss"])
    say(f"ring: ssgd step ran in {time.perf_counter() - t0:.3f} s, loss "
        f"{loss:.4f}, comm_error_bound "
        f"{float(metrics['comm_error_bound']):.6g}")
    check(math.isfinite(loss), "non-finite loss in the ring step")
    check(all(bool(jnp.all(jnp.isfinite(x)))
              for x in jax.tree.leaves((new_params, st["mu"]))),
          "non-finite params or reduced gradient after the ring step")

    # 2. the ring reduce against the exact mean of the same gradients
    grads = grads_c(params, batch)
    shards = jax.tree.leaves(grads)[0].addressable_shards
    check(len({s.device for s in shards}) == n,
          f"node gradients not over {n} devices")
    g_ring, g_exact, tele = reduce_c(grads)
    bound = float(tele.error_bound)
    # the ring's mean ends in the gradients' dtype: one rounding of it
    eps = float(jnp.finfo(model.cfg.dtype).eps)
    worst, worst_excess = 0.0, -math.inf
    for a, b in zip(jax.tree.leaves(g_ring), jax.tree.leaves(g_exact)):
        diff = jnp.abs(a.astype(jnp.float32) - b)
        slack = eps * jnp.abs(b)
        worst = max(worst, float(jnp.max(diff)))
        worst_excess = max(worst_excess, float(jnp.max(diff - slack - bound)))
    wire, dense_b = float(tele.wire_bytes), float(tele.dense_bytes)
    say(f"ring: max |ring - mean| {worst:.6g}, error_bound {bound:.6g}, "
        f"max excess over bound {worst_excess:.6g}; wire {wire:.0f} B of "
        f"{dense_b:.0f} B dense ({wire / dense_b:.4f})")
    check(bound > 0.0 and worst_excess <= 0.0,
          "ring mean outside the reducer's error_bound")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    devices = require_tpu(args.chips)
    import jax

    say(f"jax {jax.__version__}, device_kind {devices[0].device_kind!r}, "
        f"{len(devices)} device(s)")
    cache = Path(enable_compile_cache())
    warm = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    say(f"compile cache: {cache} ({warm} entries before this run)")
    t0 = time.perf_counter()
    if args.chips == 4:
        ring_reduce()
    else:
        kernel_parity()
        _, hlo = train_run()
        found = kernels_in_hlo(hlo)
        say(f"train: tpu_custom_call per kernel in the step: {found}")
        check(all(found.values()), f"kernels not compiled: {found}")
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
