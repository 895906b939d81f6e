import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init), which is why they precede the module docs.

_DOC = """Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell this lowers + compiles the real
train/prefill/serve step for the production mesh — single-pod (16, 16) and
multi-pod (2, 16, 16) — using ShapeDtypeStruct stand-ins (no allocation),
prints memory_analysis() / cost_analysis(), and extracts the roofline terms
(repro.launch.roofline). Failures (sharding mismatch, unsupported
collective) are bugs in the framework, not in the harness.

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod --out results.json
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, applicable, get_model
from repro.core.policy import DitherPolicy
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, \
    make_train_step
from repro.optim import OptConfig, init_opt_state, opt_state_specs
from repro.parallel import axes as axlib
from repro.utils import get_logger

log = get_logger("dryrun")


def _sds_with_sharding(tree, spec_tree, rules: axlib.Rules):
    shardings = axlib.spec_tree_to_shardings(spec_tree, rules, tree)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def _batch_sds(batch_specs: Dict[str, Any], rules: axlib.Rules):
    def attach(name, s):
        if s.ndim == 1:
            ax = ("batch",)
        elif s.ndim == 2:
            ax = ("batch", "seq")
        elif s.ndim == 3:
            ax = ("batch", "seq", None)
        else:
            ax = ("batch",) + (None,) * (s.ndim - 1)
        return jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=rules.sharding(ax, s.shape))

    return {k: attach(k, v) for k, v in batch_specs.items()}


def _cache_axes_for_path(path: str, ndim: int):
    if "conv" in path:  # conv window (B, K-1, conv_dim)
        return ("batch", None, "act_ssm_inner")
    if "state" in path:  # SSM state (B, H, N, P)
        return ("batch", "act_heads", None, None)
    # KV buffers (B, S_buf, KV, hd)
    return ("batch", "cache_seq", "cache_heads", None)


def _cache_sds(cache_specs, rules: axlib.Rules):
    from repro.utils.pytree import tree_map_with_path_str

    def attach(path, s):
        ax = _cache_axes_for_path(path, s.ndim)
        return jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=rules.sharding(ax, s.shape))

    return tree_map_with_path_str(attach, cache_specs)


# dense-LM families where the fsdp_seq layout measured best (§Perf qwen/It4:
# sequence-parallel activations over "model" + per-use weight gathering
# beats Megatron TP at 1M-token steps: frac 0.1019 -> 0.1184)
FSDP_SEQ_ARCHS = ("qwen2.5-32b", "gemma-2b", "gemma3-4b", "minitron-8b",
                  "internvl2-2b")


def make_rules(mesh, shape_case, arch_id: str) -> axlib.Rules:
    """Sharding ruleset per cell kind (the hillclimb edits live here)."""
    kind = shape_case.kind
    fsdp = kind == "decode" and shape_case.global_batch < 8 * mesh.shape.get(
        "data", 1)
    rules = axlib.tp_dp_rules(mesh, fsdp=fsdp)
    if kind == "decode":
        # KV cache sharded along SEQ over "model" (flash-decoding style
        # partial attention): GQA archs with kv_heads < tp-width otherwise
        # replicate the whole cache per chip column. Measured on qwen
        # decode_32k: cache 68.7 -> 4.3 GB/chip, mem_s -32%, useful +48%
        # (§Perf decode/It1).
        pass  # applied below via the cache_* mapping defaults
    if kind in ("train", "prefill") and arch_id in FSDP_SEQ_ARCHS:
        rules.mapping["seq"] = "model"
        rules.mapping["attn_seq"] = "model"
        for k in ("act_embed", "act_heads", "act_mlp", "act_vocab",
                  "act_ssm_inner", "act_expert"):
            rules.mapping[k] = None
    rules.mapping["cache_batch"] = rules.mapping["batch"]
    rules.mapping["cache_heads"] = None
    rules.mapping["cache_seq"] = "model"
    if shape_case.name == "long_500k":
        # batch=1: the data axis is idle for activations; shard the cache
        # sequence dim instead (sequence parallelism for the KV/state path)
        rules.mapping["cache_seq"] = "data"
        rules.mapping["batch"] = None
        rules.mapping["cache_batch"] = None
    if kind == "decode" and shape_case.global_batch < _axsize(mesh, ("pod", "data")):
        rules.mapping["batch"] = tuple(
            a for a in ("pod",) if a in mesh.shape) or None
    return rules


def _axsize(mesh, names) -> int:
    n = 1
    for a in names:
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str  # OK | SKIPPED | FAILED
    reason: str = ""
    compile_s: float = 0.0
    report: Optional[Dict[str, Any]] = None


def _lower_for_case(model, case, rules, policy, opt_name, memory=None):
    """Lower the real step for one cell (used for the full model AND for the
    layer-anchor cost models). Must run inside use_rules(rules)."""
    key = jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=rules.sharding((None,), (2,)))
    # eval_shape can't return the (string-typed) spec tree; capture it as a
    # trace side-effect — specs are plain Python tuples.
    spec_box = {}

    def _init_params_only(k):
        p, s = model.init(k)
        spec_box["specs"] = s
        return p

    params_shape = jax.eval_shape(_init_params_only, jax.random.PRNGKey(0))
    specs = spec_box["specs"]
    params_sds = _sds_with_sharding(params_shape, specs, rules)

    if case.kind == "train":
        opt_cfg = OptConfig(name=opt_name)
        opt_shape = jax.eval_shape(
            lambda p: init_opt_state(p, opt_cfg), params_shape)
        opt_sds = _sds_with_sharding(
            opt_shape, opt_state_specs(specs, opt_cfg), rules)
        batch_sds = _batch_sds(
            model.train_batch_specs(case.global_batch, case.seq_len), rules)
        step = make_train_step(model, opt_cfg, policy, memory=memory)
        return jax.jit(step).lower(params_sds, opt_sds, batch_sds, key)
    if case.kind == "prefill":
        batch_sds = _batch_sds(
            model.train_batch_specs(case.global_batch, case.seq_len), rules)
        step = make_prefill_step(model)
        return jax.jit(step).lower(params_sds, batch_sds)
    # decode
    cache_sds = _cache_sds(
        model.cache_specs(case.global_batch, case.seq_len), rules)
    tok = jax.ShapeDtypeStruct(
        (case.global_batch, 1), jnp.int32,
        sharding=rules.sharding(("batch", None), (case.global_batch, 1)))
    t_sds = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(model)
    return jax.jit(step).lower(params_sds, cache_sds, tok, t_sds)


def _residual_memory_stats(model, case, policy, memory, n_chips: int,
                           mem_stats: Dict[str, Any]) -> Dict[str, float]:
    """Residual-footprint accounting for one train cell: eval_shape the
    loss with a recorder ctx (no FLOPs), price the stored/dense totals
    against per-chip HBM, and estimate the max batch the cell supports
    under each store (repro.memory.accounting + costmodel.price_memory)."""
    from repro.launch import costmodel
    from repro.memory.accounting import footprint_totals, residual_report

    params_sds = jax.eval_shape(lambda k: model.init(k)[0],
                                jax.random.PRNGKey(0))
    batch_sds = model.train_batch_specs(case.global_batch, case.seq_len)
    report = residual_report(
        lambda p, b, c: model.loss(p, b, ctx=c), params_sds, batch_sds,
        policy=policy, memory=memory)
    stored, dense = footprint_totals(report)
    if dense <= 0:  # policy covers no layers -> autodiff owns residuals
        return {}
    priced = costmodel.price_memory(
        stored, dense, n_chips=n_chips, batch=case.global_batch,
        fixed_bytes_per_chip=float(mem_stats.get("argument_bytes", 0)))
    out = {"residual_layers": float(len(report)),
           "residual_stored_bytes": float(stored),
           "residual_dense_bytes": float(dense)}
    # keep the artifacts strict-JSON-safe: drop inf/nan estimates
    out.update({k: v for k, v in priced.items() if np.isfinite(v)})
    return out


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
             policy: Optional[DitherPolicy] = None,
             rules_override=None, opt_name: str = "adamw",
             correct_costs: bool = True, model_override=None,
             memory=None, verbose: bool = True) -> CellResult:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    case = SHAPES[shape_name]
    model = model_override if model_override is not None else get_model(arch_id)
    skip = applicable(arch_id, shape_name, model.has_decode)
    if skip:
        return CellResult(arch_id, shape_name, mesh_name, "SKIPPED", skip)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rules = (rules_override or make_rules)(mesh, case, arch_id)

    t0 = time.time()
    try:
        with axlib.use_rules(rules):
            lowered = _lower_for_case(model, case, rules, policy, opt_name,
                                      memory=memory)
            compiled = lowered.compile()
        compile_s = time.time() - t0
        cost = dict(compiled.cost_analysis())
        mem = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        }
        hlo = compiled.as_text()
        cost_dbg = {}
        if correct_costs and case.kind in ("train", "prefill") \
                and getattr(model.cfg, "n_layers", 1) > 2:
            # scan bodies are counted ONCE by XLA cost analysis: re-derive
            # totals from unrolled 1-2 layer anchors (launch/costmodel.py)
            from repro.launch import costmodel

            def anchor_lower(m):
                with axlib.use_rules(rules):
                    return _lower_for_case(m, case, rules, policy, opt_name,
                                           memory=memory)

            totals, cost_dbg = costmodel.corrected_costs(model, anchor_lower)
            cost["flops"] = totals["flops"]
            cost["bytes accessed"] = totals["bytes"]
            cost_dbg["corrected_wire"] = totals["wire"]
            cost_dbg["corrected_naive"] = totals["naive"]
        report = rl.analyze(
            arch=arch_id, shape=shape_name, mesh_name=mesh_name,
            n_chips=n_chips, cost=cost, hlo_text=hlo,
            model_flops_global=rl.model_flops(
                case.kind, model.active_param_count, case.seq_len,
                case.global_batch),
            memory_stats=mem_stats)
        if cost_dbg:
            report.wire_bytes_per_chip = cost_dbg["corrected_wire"]
            report.naive_collective_bytes = cost_dbg["corrected_naive"]
            report.collective_s = report.wire_bytes_per_chip / rl.ICI_BW
            terms = {"compute": report.compute_s, "memory": report.memory_s,
                     "collective": report.collective_s}
            report.dominant = max(terms, key=terms.get)
            bound = max(terms.values())
            report.roofline_fraction = (
                report.model_flops_global / (n_chips * rl.PEAK_BF16_FLOPS)
            ) / max(bound, 1e-30)
            report.useful_ratio = report.model_flops_global / max(
                report.flops_per_chip * n_chips, 1.0)
            report.memory_stats["cost_anchors"] = str(cost_dbg.get("anchors"))
        if case.kind == "train" and policy is not None:
            try:
                report.memory_stats.update(_residual_memory_stats(
                    model, case, policy, memory, n_chips, mem_stats))
            except Exception as e:  # noqa: BLE001 — accounting is advisory
                report.memory_stats["residual_error"] = (
                    f"{type(e).__name__}: {e}")
        if verbose:
            log.info(
                "%s x %s [%s] OK compile=%.1fs flops/chip=%.3e bytes/chip=%.3e "
                "wire/chip=%.3e dominant=%s frac=%.3f",
                arch_id, shape_name, mesh_name, compile_s,
                report.flops_per_chip, report.bytes_per_chip,
                report.wire_bytes_per_chip, report.dominant,
                report.roofline_fraction)
            log.info("memory_analysis: %s", mem_stats)
        return CellResult(arch_id, shape_name, mesh_name, "OK",
                          compile_s=compile_s, report=report.row())
    except Exception as e:  # noqa: BLE001 — report, don't crash the grid
        if verbose:
            traceback.print_exc()
        return CellResult(arch_id, shape_name, mesh_name, "FAILED",
                          reason=f"{type(e).__name__}: {e}",
                          compile_s=time.time() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--dither", choices=["off", "paper", "int8", "row"],
                    default="paper")
    ap.add_argument("--program", default="",
                    help="unified run program with 'dither:'/'memory:'/"
                    "'comm:' sections (see repro.launch.program); the "
                    "dither section drives the lowered step, the memory "
                    "section the residual accounting, and the comm "
                    "section is validated + recorded in the run context")
    ap.add_argument("--policy-program", default="",
                    help="DEPRECATED: use --program \"dither: ...\" (see "
                    "repro.core.schedule.parse_program)")
    ap.add_argument("--memory-program", default="",
                    help="DEPRECATED: use --program \"memory: ...\" (see "
                    "repro.memory)")
    ap.add_argument("--out", default="")
    ap.add_argument("--run-dir", default="",
                    help="observability run directory: each cell's "
                    "lower+compile wall-clock lands in the phase stream, "
                    "renderable offline via "
                    "'python -m repro.obs.report <run-dir>'")
    args = ap.parse_args()

    from repro.launch.program import format_program, merge_legacy_flags

    spec = merge_legacy_flags(args.program, args.policy_program,
                              args.memory_program)
    policy = None if args.dither == "off" else DitherPolicy(variant=args.dither)
    if spec.dither:
        policy = spec.dither_program(
            policy if policy is not None else DitherPolicy(variant="off"))
    memory = spec.memory_policy()
    spec.comm_policy()  # validate the comm section even though the grid
    # itself prices wire bytes from the lowered HLO, not the CommPolicy
    cells = []
    if args.all:
        targets = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        targets = [(args.arch, args.shape)]
    runlog = None
    if args.run_dir:
        from repro.obs.runlog import RunLog

        runlog = RunLog(args.run_dir, context={
            "tool": "dryrun", "dither": args.dither,
            "program": format_program(spec)})
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    from repro.obs.trace import get_tracer, span

    for i, (arch, shape) in enumerate(targets):
        get_tracer().set_step(i)
        for mp in meshes:
            # the roofline table is single-pod only; multi-pod cells just
            # prove the "pod" axis lowers, so skip the anchor compiles there
            with span("cell"), span(f"{arch}:{shape}"):
                res = run_cell(arch, shape, multi_pod=mp, policy=policy,
                               memory=memory, correct_costs=not mp)
            cells.append(dataclasses.asdict(res))
            print(f"{res.arch:22s} {res.shape:12s} {res.mesh:8s} "
                  f"{res.status:8s} {res.reason[:80]}")
    n_ok = sum(c["status"] == "OK" for c in cells)
    n_fail = sum(c["status"] == "FAILED" for c in cells)
    n_skip = sum(c["status"] == "SKIPPED" for c in cells)
    print(f"\ntotal={len(cells)} ok={n_ok} skipped={n_skip} failed={n_fail}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(cells, f, indent=1)
        print(f"wrote {args.out}")
    if runlog is not None:
        runlog.close()
        print(f"run dir: {args.run_dir} "
              f"(render: python -m repro.obs.report {args.run_dir})")


if __name__ == "__main__":
    main()
