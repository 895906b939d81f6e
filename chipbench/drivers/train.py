"""One-chip training through the program's Trainer.

The trainer is built as ``repro.launch.train.run`` builds it from the
workload's launcher flags, with the model at the configuration file's sizes
and the weights the benchmark makes from the seed. Set-up runs the first
three steps through ``Trainer.fit`` on the benchmark's batches; they compile
the step, and the program's loss, first gradient and parameter change are
read from them for the check. The window is one further ``fit`` call of as
many steps as the checked steps' time puts in ``--seconds``, as the
launcher's loop runs them, and ends when the last step has completed. After
it, the program's state is freed and the plain reference follows the same
three steps.
"""
from __future__ import annotations

import gc
import sys
import time

CHECKED_STEPS = 3


def launcher_flags(workload: dict):
    """(flags, program spec) as ``repro.launch.train`` parses the workload's
    launcher flags."""
    from repro.launch import train as launcher
    from repro.launch.program import merge_legacy_flags

    args = launcher.build_parser().parse_args(workload["launcher"])
    spec = merge_legacy_flags(args.program, args.policy_program,
                              args.memory_program)
    if spec.memory or spec.comm or spec.quant:
        raise SystemExit("chipbench: the train driver runs 'dither:' "
                         "programs only")
    return args, spec


def reference_dither_s(workload: dict):
    """The dither scale the reference runs on every dense contraction:
    the flags' ``--s`` where the base policy dithers, else None."""
    args, spec = launcher_flags(workload)
    if args.dither == "off":
        if spec.dither:
            raise SystemExit("chipbench: the reference dithers every dense "
                             "contraction or none; give a base --dither")
        return None
    return args.s


def build_trainer(cell, model):
    """The trainer ``repro.launch.train.run`` builds for these flags."""
    from repro.core.policy import DitherPolicy
    from repro.optim import OptConfig
    from repro.train import Trainer, TrainerConfig

    args, spec = launcher_flags(cell.workload)
    policy = (None if args.dither == "off"
              else DitherPolicy(variant=args.dither, s=args.s))
    if spec.dither:
        policy = spec.dither_program(
            policy or DitherPolicy(variant="off", s=args.s))
    opt = OptConfig(name="adamw", lr=args.lr, schedule="cosine",
                    warmup_steps=max(args.steps // 20, 1),
                    total_steps=args.steps)
    # the reference runs the optimizer the workload file states: the
    # program's must be the same one
    for k, v in cell.workload["optimizer"].items():
        if getattr(opt, k) != v:
            raise SystemExit(f"chipbench: the program's AdamW {k} is "
                             f"{getattr(opt, k)!r}, the workload states {v!r}")
    tcfg = TrainerConfig(total_steps=args.steps, grad_accum=args.grad_accum,
                         log_every=max(args.steps // 10, 1))
    return Trainer(model, opt, tcfg, policy=policy), tcfg.log_every


def seed_key(seed: int):
    """A raw PRNG key from a seed of any size."""
    import jax.numpy as jnp
    import numpy as np

    return jnp.asarray(np.random.SeedSequence(seed).generate_state(2),
                       jnp.uint32)


def run(cell, devices, compiles, trace_dir) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import check
    from chipbench.tokens import TokenStream
    from repro.kernels.ops import KERNEL_FALLBACKS
    from repro.optim import init_opt_state

    fam, cfg, wl = cell.family(), cell.config, cell.workload
    model = fam.build(cfg)
    trainer, log_every = build_trainer(cell, model)
    key = seed_key(cell.seed)
    make_params = jax.jit(lambda k: fam.init(cfg, k))
    want = jax.eval_shape(lambda k: model.init(k)[0], key)
    if jax.tree.map(lambda s: (s.shape, s.dtype), want) != jax.tree.map(
            lambda a: (a.shape, a.dtype), jax.eval_shape(make_params, key)):
        raise SystemExit("chipbench: the program's parameter layout differs "
                         "from the benchmark's weights")
    params = make_params(key)
    opt_state = jax.jit(init_opt_state, static_argnums=1)(params,
                                                           trainer.opt_cfg)
    st = wl["stream"]
    stream = TokenStream(cfg["vocab_size"], st["batch"], st["seq_len"],
                         cell.seed, st["zipf_a"])
    tokens_per_step = st["batch"] * st["seq_len"]

    def batches(start):
        step = start
        while True:
            with cell.span("data"):
                b = {k: jnp.asarray(v) for k, v in stream(step).items()}
            step += 1
            yield b

    feed = batches(0)
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for a in jax.tree.leaves(t)])

    def fit_to(total):
        nonlocal params, opt_state
        trainer.tcfg.total_steps = total
        out = trainer.fit(feed, params, opt_state)
        params, opt_state = out["params"], out["opt_state"]

    # set-up: the checked steps, logged one by one
    trainer.tcfg.log_every = 1
    fit_to(1)
    b1 = trainer.opt_cfg.b1
    grad_norms = [float(g) / (1 - b1) for g in norms(opt_state["mu"])]
    t = time.perf_counter()
    fit_to(CHECKED_STEPS)
    step_s = (time.perf_counter() - t) / (CHECKED_STEPS - 1)
    # the parameters that step 4 takes: AdamW's f32 master copy where the
    # parameter is stored in lower precision
    moved = jax.tree.map(
        lambda p, m: p if m.ndim == 0 else m, params, opt_state["master"])
    change_norms = [float(c) for c in jax.jit(lambda a, b: norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))(
            moved, make_params(key))]
    prog = {"losses": [r["loss"] for r in trainer.history],
            "grad_norms": grad_norms, "change_norms": change_norms}
    trainer.tcfg.log_every = log_every
    jax.block_until_ready((params, opt_state))
    gc.collect()  # set-up's garbage, so that no collection of it falls later
    setup_s = time.perf_counter() - cell.t_start

    # the window: one fit call, the host dispatching ahead of the device
    steps = max(1, round(cell.seconds / step_s))
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    compiles.active = True
    t0 = time.perf_counter()
    with cell.span("window"):
        with cell.span("dispatch"):
            fit_to(CHECKED_STEPS + steps)
        with cell.span("sync"):
            jax.block_until_ready((params, opt_state))
    window_s = time.perf_counter() - t0
    compiles.active = False
    if trace_dir:
        jax.profiler.stop_trace()
    # the runtime's peak counts buffers but not the step program's scratch
    # (HLO temp), which it holds apart while a step runs: the state held in
    # the window plus that scratch is the step's footprint
    stats = [d.memory_stats() or {} for d in devices]
    t = time.perf_counter()
    with cell.span("check"):
        scratch = trainer.lower_step(params, opt_state, {
            k: jnp.asarray(v) for k, v in stream(0).items()}).compile(
            ).memory_analysis().temp_size_in_bytes
    peak = max(max(s.get("peak_bytes_in_use", 0),
                   s.get("bytes_in_use", 0) + scratch) for s in stats)
    print(f"chipbench: device memory {stats}, step scratch {scratch} bytes "
          f"(read in {time.perf_counter() - t!r} s)", file=sys.stderr)

    # free the program's state, then the reference follows the checked steps
    del params, opt_state, moved
    trainer.history.clear()
    gc.collect()
    with cell.span("check"):
        ref = cell.reference().train_readings(
            make_params(key),
            [{k: jnp.asarray(v) for k, v in stream(i).items()}
             for i in range(CHECKED_STEPS)], cfg, wl["optimizer"],
            dither_s=reference_dither_s(wl), key=jax.random.fold_in(key, 1))
    print(f"chipbench: checked steps {step_s!r} s each; program {prog}, "
          f"reference {ref}", file=sys.stderr)
    numbers = check.train_numbers(prog, ref)
    numbers["kernel_fallbacks"] = sum(KERNEL_FALLBACKS.values())
    return {"setup_s": setup_s, "window_s": window_s, "steps": steps,
            "attempted": steps, "tokens": steps * tokens_per_step,
            "memory_peak_bytes": peak, "window_compiles": compiles.count,
            "numbers": numbers}
