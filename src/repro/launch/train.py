"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
        --preset smoke --steps 50 --dither paper --s 2.0

Presets:
    smoke  — the arch's reduced config, tiny batch (CPU-runnable)
    full   — the arch's published config. Configs whose weights, AdamW
             state and activations fit one chip run on it (mamba2-370m on
             one v5e: ``python chip_smoke.py`` drives exactly this path);
             larger ones need a sharded deployment.

On a multi-host cluster, call jax.distributed.initialize() via
--distributed (standard TPU pod env) before anything touches devices.
"""
from __future__ import annotations

import argparse
from typing import List, Optional

import jax

from repro.configs import ARCH_IDS, get_model, get_smoke_model
from repro.core.policy import DitherPolicy
from repro.data import TokenStreamConfig, token_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.program import format_program, merge_legacy_flags
from repro.optim import OptConfig
from repro.train import Trainer, TrainerConfig
from repro.utils import get_logger

log = get_logger("train")


def batch_fn_for(model, batch: int, seq: int):
    cfg = model.cfg
    vocab = getattr(cfg, "vocab", 512)
    tcfg = TokenStreamConfig(vocab=vocab, seq_len=seq, batch=batch)

    def fn(step: int):
        b = token_batch(tcfg, step)
        if model.family == "audio":
            import jax.numpy as jnp
            import numpy as np
            rng = np.random.default_rng(step)
            b["frames"] = jnp.asarray(rng.normal(
                0, 1, (batch, cfg.n_frames, cfg.d_model)).astype(np.float32))
        if model.family == "vlm" and cfg.vlm_patches:
            import jax.numpy as jnp
            import numpy as np
            rng = np.random.default_rng(step)
            b["patch_embeds"] = jnp.asarray(rng.normal(
                0, 1, (batch, cfg.vlm_patches, cfg.vit_dim)).astype(np.float32))
        return b

    return fn


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--preset", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dither", choices=["off", "paper", "int8", "row",
                                         "meprop"], default="paper")
    ap.add_argument("--s", type=float, default=2.0)
    ap.add_argument("--program", default="",
                    help="unified run program with 'dither:'/'memory:'/"
                    "'comm:'/'quant:' sections, e.g. \"dither: phase@0=off;"
                    "phase@30=paper;rule lm_head:off memory: default=nsd;"
                    "rule fc0:int8 comm: topology=butterfly;pods=4;"
                    "bucket_bytes=1048576 quant: grad=int4@g32;mu=m8;"
                    "nu=u8\" (see repro.launch.program). "
                    "The dither section builds on --dither/--s as the "
                    "base policy; the comm section attaches a gradient "
                    "CommPolicy to the trainer; the quant section picks "
                    "registered codecs per surface (grad/wire/resid/mu/nu, "
                    "see repro.quant.program).")
    ap.add_argument("--policy-program", default="",
                    help="DEPRECATED: use --program \"dither: ...\". "
                    "Per-layer/step policy program spec "
                    "(see repro.core.schedule).")
    ap.add_argument("--memory-program", default="",
                    help="DEPRECATED: use --program \"memory: ...\". "
                    "Per-layer residual-memory spec (see repro.memory).")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--run-dir", default="",
                    help="observability run directory: drains the metrics "
                    "bus (dither/comm/memory/phase/train/monitor streams) "
                    "into JSONL + a provenance manifest; render with "
                    "'python -m repro.obs.report <run-dir>'")
    ap.add_argument("--escalate-monitors", action="store_true",
                    help="with --run-dir: critical health events (NaN "
                    "loss, sparsity collapse) raise instead of warn")
    ap.add_argument("--distributed", action="store_true")
    return ap


def run(args: argparse.Namespace) -> dict:
    """Train as ``args`` says; returns ``Trainer.fit``'s output plus the
    ``trainer`` itself (its step can be lowered again for inspection)."""
    model = (get_smoke_model if args.preset == "smoke" else get_model)(
        args.arch)
    spec = merge_legacy_flags(args.program, args.policy_program,
                              args.memory_program)
    qo = spec.quant_overrides()
    policy = (None if args.dither == "off"
              else DitherPolicy(variant=args.dither, s=args.s))
    if qo is not None and qo.grad is not None:
        # applied to the BASE policy so dither-program phases/rules inherit
        # the cotangent codec (schedule.resolve_layer carries base.grad_codec)
        policy = ((policy or DitherPolicy(variant="off", s=args.s))
                  .replace(grad_codec=qo.grad))
    if spec.dither:
        # --dither off stays off as the base: only explicit program clauses
        # (phases / rule variants) re-enable dithering
        base = (policy if policy is not None
                else DitherPolicy(variant="off", s=args.s))
        policy = spec.dither_program(base)
    comm_policy = spec.comm_policy()
    memory_program = spec.memory
    if qo is not None:
        if qo.wire is not None:
            from repro.comm import CommPolicy

            comm_policy = (comm_policy.replace(default=qo.wire)
                           if comm_policy is not None
                           else CommPolicy(default=qo.wire))
        if qo.resid is not None:
            if memory_program:
                raise ValueError(
                    "quant: resid= conflicts with the 'memory:' section "
                    "(its default= clause); specify one")
            memory_program = f"default={qo.resid}"
    obs = None
    if args.run_dir:
        from repro.obs import run_obs

        obs = run_obs(
            args.run_dir,
            context={"tool": "train", "arch": args.arch,
                     "preset": args.preset, "steps": args.steps,
                     "dither": args.dither, "s": args.s,
                     "program": format_program(spec)},
            escalate=args.escalate_monitors)
    trainer = Trainer(
        model,
        OptConfig(name="adamw", lr=args.lr, schedule="cosine",
                  warmup_steps=max(args.steps // 20, 1),
                  total_steps=args.steps,
                  mu_codec=qo.mu if qo is not None else None,
                  nu_codec=qo.nu if qo is not None else None),
        TrainerConfig(total_steps=args.steps, grad_accum=args.grad_accum,
                      log_every=max(args.steps // 10, 1),
                      ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every),
        policy=policy,
        comm_policy=comm_policy,
        memory_policy=memory_program or None,
        obs=obs,
    )
    fn = batch_fn_for(model, args.batch, args.seq)
    counter = iter(range(10**9))

    def it():
        while True:
            yield fn(next(counter))

    out = trainer.fit(it())
    log.info("final loss: %.4f",
             out["history"][-1]["loss"] if out["history"] else float("nan"))
    if args.run_dir:
        log.info("run dir: %s (render: python -m repro.obs.report %s)",
                 args.run_dir, args.run_dir)
    return dict(out, trainer=trainer)


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    if args.distributed:
        jax.distributed.initialize()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
