"""Training runtime: loop + grad accumulation + checkpoints + fault hooks.

Single-host (tests/examples) and pjit multi-device paths share this loop;
distribution enters only through the sharding rules installed around jit.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.comm.compression import CommPolicy, init_comm_state
from repro.comm.reducer import reducer as comm_reducer
from repro.core.dithered import TALLY_FIELDS
from repro.core.policy import DitherCtx, DitherPolicy
from repro.core.schedule import (ControllerDriver, PolicyProgram, as_program,
                                 discover_layer_names)
from repro.models.api import Model
from repro.obs.bus import get_bus
from repro.obs.streams import TALLY
from repro.obs.trace import (annotate, keep_scopes_in_compile_cache,
                              profile_span, step_span)
from repro.optim import OptConfig, apply_updates, init_opt_state
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import PreemptionGuard
from repro.utils import get_logger

log = get_logger("trainer")


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    grad_accum: int = 1
    log_every: int = 10
    ckpt_every: int = 0  # 0 = off
    ckpt_dir: str = ""
    keep_ckpts: int = 3
    seed: int = 0


class Trainer:
    def __init__(self, model: Model, opt_cfg: OptConfig, tcfg: TrainerConfig,
                 policy: Optional[DitherPolicy | PolicyProgram] = None,
                 eval_fn: Optional[Callable] = None,
                 comm_policy: Optional[CommPolicy] = None,
                 topology=None, memory_policy=None, obs=None):
        from repro.memory.policy import as_memory_policy

        self.model = model
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        # a plain DitherPolicy is lifted into the degenerate PolicyProgram;
        # every step resolves per layer through the program path.
        self.policy = policy
        self.program = as_program(policy)
        # repro.memory MemoryPolicy (or spec string): residual codec /
        # remat per dithered layer. Static — baked into the jitted step's
        # closure; set it before fit(), not mid-run.
        self.memory_policy = as_memory_policy(memory_policy)
        self.eval_fn = eval_fn
        # gradient wire path: accumulated grads go through one
        # repro.comm.reducer built here (flat single-participant wire
        # model; bucket_bytes > 0 adds overlap scheduling transparently).
        # _comm_state holds the error-feedback residuals; it rides in the
        # checkpoint tree so a preempted topk_ef run resumes losslessly.
        self.comm_policy = comm_policy
        self._reducer = (comm_reducer(comm_policy, n_nodes=1, stacked=False)
                         if comm_policy is not None else None)
        # launch.mesh.NodeTopology of the deployment this run models: each
        # logged history row prices the step's measured wire bytes on the
        # fast (ICI) and, when the topology spans pods, slow (DCN) axis.
        self.topology = topology
        self._comm_state: Optional[Dict[str, Any]] = None
        # closed-loop sparsity controller: shared host-side protocol
        # (discover -> traced state -> per-step tick); the state rides the
        # checkpoint tree next to the EF residuals, the telemetry cursor is
        # host-only (re-measured from scratch on resume)
        self._ctrl = ControllerDriver(self.program)
        # repro.obs.RunObs: when set, the loop records step-phase spans
        # (data/dispatch/controller/checkpoint), per-step train metrics,
        # and monitor ticks, and drains everything into the run directory.
        # None keeps the loop observability-free (no per-step host sync).
        self.obs = obs
        self.guard = PreemptionGuard(install=False)
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep_ckpts)
                     if tcfg.ckpt_every and tcfg.ckpt_dir else None)
        # phase_policy is static: a PolicyProgram phase boundary retraces
        # exactly once; knob schedules / controller nudges are traced and
        # re-use the compiled step (tests/test_schedule.py pins this).
        # params and optimizer state are donated: the step's outputs take
        # over their buffers, so a step holds one copy of the model state
        # (the old ones are never read again; checkpoints copy to host
        # before the next step runs)
        self._jit_step = jax.jit(self._step, static_argnames=("phase_policy",),
                                 donate_argnames=("params", "opt_state"))
        # the step's named scopes attribute its device time in a profile:
        # a cached executable of another version must not stand in for it
        keep_scopes_in_compile_cache()
        self.history: list = []

    # one optimizer step with optional micro-batch gradient accumulation
    def _step(self, params, opt_state, batches, base_key, comm_state,
              ctrl_state, phase_policy):
        step = opt_state["step"]
        ctx = None
        if phase_policy is not None and self.program.step_enabled(phase_policy):
            ctx = DitherCtx.for_step(base_key, step, phase_policy,
                                     program=self.program,
                                     ctrl=ctrl_state or None,
                                     memory=self.memory_policy)

        def one_loss(p, t, b, i):
            c = None
            if ctx is not None:
                # micro-batches get distinct noise: fold the slice index in
                c = dataclasses.replace(
                    ctx.with_key(jax.random.fold_in(ctx.key, i)), tally=t)
            return self.model.loss(p, b, ctx=c)

        grad_fn = jax.value_and_grad(one_loss, argnums=(0, 1))
        n = self.tcfg.grad_accum
        if n == 1:
            tally = self._tally(ctx, params, batches)
            with annotate("step/grad"):
                loss, (grads, counts) = grad_fn(params, tally, batches, 0)
        else:
            # accept flat batches: split the leading (batch) dim into
            # (n, batch/n, ...) microbatches
            def to_micro(x):
                if x.shape[0] == n:
                    return x
                assert x.shape[0] % n == 0, (x.shape, n)
                return x.reshape((n, x.shape[0] // n) + x.shape[1:])

            batches = jax.tree.map(to_micro, batches)
            tally = self._tally(ctx, params,
                                jax.tree.map(lambda x: x[0], batches))

            def acc_fn(carry, ib):
                i, b = ib
                lv, (g, c) = grad_fn(params, tally, b, i)
                loss_acc, g_acc, c_acc = carry
                # the counts sum over micro-batches: each ran its backward
                return (loss_acc + lv / n,
                        jax.tree.map(lambda a, x: a + x / n, g_acc, g),
                        jax.tree.map(jnp.add, c_acc, c)), None

            zero = (jnp.zeros(()),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params),
                    tally)
            with annotate("step/grad"):
                (loss, grads, counts), _ = jax.lax.scan(
                    acc_fn, zero, (jnp.arange(n), batches))
        if self._reducer is not None:
            # the reducer folds the step in; the 0xC033 salt keeps the
            # comm keys in the same stream they were pre-redesign, so
            # resumed runs and pinned tests stay bit-exact
            comm_key = jax.random.fold_in(base_key, 0xC033)
            with annotate("step/comm"):
                grads, tele, comm_state = self._reducer.reduce(
                    grads, comm_key, step, comm_state)
            metrics_comm = {"comm_wire_bytes": tele.wire_bytes,
                            "comm_dense_bytes": tele.dense_bytes}
        else:
            metrics_comm = {}
        with annotate("step/update"):
            params, opt_state, metrics = apply_updates(
                params, grads, opt_state, self.opt_cfg)
        metrics["loss"] = loss
        metrics.update(metrics_comm)
        if counts is not None:
            total = sum(jax.tree.leaves(counts))
            metrics.update({f"dither_{k}": total[i]
                            for i, k in enumerate(TALLY_FIELDS)})
        return params, opt_state, metrics, comm_state

    def _tally(self, ctx, params, batch):
        """Zero tally inputs, one per layer name that consults the policy
        (found by an abstract trace of the loss), or None without a ctx.
        Their gradients count the kernel path's work
        (repro.core.dithered.TALLY_FIELDS); the loss and the other
        gradients do not depend on them."""
        if ctx is None:
            return None
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,
                                                             a.dtype),
                              (params, batch))
        names = discover_layer_names(
            lambda p, b, c: self.model.loss(p, b, ctx=c), *shapes)
        return {name: jnp.zeros((len(TALLY_FIELDS),), jnp.float32)
                for name in names}

    def _init_comm_state(self, params) -> Dict[str, Any]:
        return (init_comm_state(params, self.comm_policy)
                if self.comm_policy is not None else {})

    def _ckpt_tree(self, params, opt_state) -> Dict[str, Any]:
        tree = {"params": params, "opt": opt_state}
        if self._comm_state:
            tree["comm"] = self._comm_state
        if self._ctrl.state:
            tree["ctrl"] = self._ctrl.state
        return tree

    def _init_ctrl_state(self, params, batch) -> None:
        """One-time controller setup (idempotent via the driver's flag).

        Layer names are discovered by an eval_shape trace of the loss (no
        FLOPs) so the {layer: log-scale} dict is complete before step 0 —
        growing it mid-run would change the jitted step's input structure
        and force a retrace."""
        if not self._ctrl.active or self._ctrl.ready:
            return
        names = self._ctrl.ensure_init(
            lambda p, b, ctx: self.model.loss(p, b, ctx=ctx), params, batch)
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            # the main restore ran before the batch (and thus the layer
            # names) existed; pick the controller subtree up now
            try:
                self._ctrl.state = self.ckpt.restore(
                    {"ctrl": self._ctrl.state})["ctrl"]
                log.info("restored controller state")
            except KeyError:
                pass  # checkpoint predates the controller: scales restart at 1
        log.info("sparsity controller: %d layers under control", len(names))

    def restore_or_init(self, key: jax.Array):
        params, specs = self.model.init(key)
        opt_state = init_opt_state(params, self.opt_cfg)
        self._comm_state = self._init_comm_state(params)
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            try:
                state = self.ckpt.restore(self._ckpt_tree(params, opt_state))
            except KeyError:
                # checkpoint predates the comm subtree: residuals restart at 0
                state = self.ckpt.restore({"params": params,
                                           "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            self._comm_state = state.get("comm", self._comm_state)
            # controller state is restored later, in _init_ctrl_state: its
            # template needs the layer names, which need the first batch
            log.info("restored checkpoint at step %d",
                     int(opt_state["step"]))
        return params, opt_state, specs

    def _phase_policy(self, step: int):
        return (self.program.phase_policy_at(step)
                if self.program is not None else None)

    def lower_step(self, params, opt_state, batch, *, step: int = 0):
        """Lower the jitted step for these inputs without running it.

        Inputs may be arrays or ``jax.ShapeDtypeStruct``s. ``.compile()``
        on the result gives the executable's HLO text and memory analysis.
        """
        base_key = jax.eval_shape(
            lambda: jax.random.fold_in(jax.random.PRNGKey(0), 0xD17E))
        comm_state = jax.eval_shape(self._init_comm_state, params)
        return self._jit_step.lower(
            params, opt_state, batch, base_key, comm_state, self._ctrl.state,
            phase_policy=self._phase_policy(step))

    def fit(self, batch_iter: Iterator, params=None, opt_state=None
            ) -> Dict[str, Any]:
        """Train to ``tcfg.total_steps``.

        Returns ``params``, ``opt_state``, ``history`` (the logged rows) and
        ``metrics``: the last step's metrics as device arrays (None when no
        step ran), read without a host sync. A dithered step's kernel-path
        counters (``dither_tiles_live``, ``dither_tiles``, ``dither_zeros``,
        ``dither_elements``) are among them and are also recorded, unread,
        as one row of the bus's ``tally`` stream per call.
        """
        key = jax.random.PRNGKey(self.tcfg.seed)
        base_key = jax.random.fold_in(key, 0xD17E)
        if params is None:
            params, opt_state, _ = self.restore_or_init(key)
        start = int(opt_state["step"])
        if self._comm_state is None:  # caller passed params directly
            self._comm_state = self._init_comm_state(params)
        comm_state = self._comm_state
        # span factory: with obs attached every phase is timed into the
        # "phase" stream; without it the spans only mark the profiler's
        # timeline (no bus row, no host sync)
        sp = self.obs.span if self.obs is not None else profile_span
        metrics = None
        t0 = time.time()
        for step in range(start, self.tcfg.total_steps):
            with step_span(step):
                if self.obs is not None:
                    self.obs.set_step(step)
                if self.guard.should_stop:
                    log.info("preemption: checkpointing at step %d and "
                             "exiting", step)
                    if self.ckpt is not None:
                        with sp("checkpoint"):
                            self.ckpt.save(
                                step, self._ckpt_tree(params, opt_state))
                            self.ckpt.wait()
                    break
                with sp("data"):
                    batch = next(batch_iter)
                    if isinstance(batch, tuple):  # (step, batch) loaders
                        batch = batch[1]
                self._init_ctrl_state(params, batch)
                phase_policy = self._phase_policy(step)
                with sp("dispatch"):
                    params, opt_state, metrics, comm_state = self._jit_step(
                        params, opt_state, batch, base_key, comm_state,
                        self._ctrl.state, phase_policy=phase_policy)
                self._comm_state = comm_state
                # controller tick: fold the step's per-layer telemetry into
                # the log-scales (host-side; the updated state is a traced
                # input next step, so no retrace)
                with sp("controller"):
                    self._ctrl.tick()
                if self.obs is not None:
                    # float() blocks on the step's device values —
                    # acceptable only because obs is opt-in; monitors + run
                    # log need host scalars
                    self.obs.on_step(
                        step + 1, {k: float(v) for k, v in metrics.items()})
                if (self.tcfg.log_every
                        and (step + 1) % self.tcfg.log_every == 0):
                    loss = float(metrics["loss"])
                    # time_s: wall seconds since the loop started, read once
                    # this step's loss has reached the host
                    row = {"step": step + 1, "loss": loss,
                           "time_s": time.time() - t0}
                    if "comm_wire_bytes" in metrics:
                        wire = float(metrics["comm_wire_bytes"])
                        row["comm_wire_mb"] = wire / 1e6
                        if self.topology is not None:
                            from repro.launch.costmodel import \
                                price_step_comm
                            row.update(price_step_comm(
                                wire, pods=self.topology.pods))
                    self.history.append(row)
                    log.info("step %d loss %.4f (%.2f s)", step + 1, loss,
                             row["time_s"])
                if (self.ckpt is not None and self.tcfg.ckpt_every
                        and (step + 1) % self.tcfg.ckpt_every == 0):
                    with sp("checkpoint"):
                        self.ckpt.save(step + 1,
                                       self._ckpt_tree(params, opt_state))
        if self.ckpt is not None:
            self.ckpt.wait()
        if self.obs is not None:
            self.obs.finish()
        if metrics is not None and "dither_tiles" in metrics:
            get_bus().record(TALLY.name, "train", jnp.stack(
                [metrics[f"dither_{k}"] for k in TALLY_FIELDS]))
        return {"params": params, "opt_state": opt_state,
                "history": self.history, "metrics": metrics}
