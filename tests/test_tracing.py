"""In-program measurement: named scopes in the step program, the tally of
the kernel path's work, and the trainer's spans on the profiler's clock.

A 2-layer Mamba-2 at tiny widths trains with ``L.ssm.*`` on the kernel
variant (Pallas in interpret mode) and the head on the paper variant, as the
chip benchmark's dither cell does at full size.
"""
import dataclasses
import re
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dithered
from repro.core.dithered import TALLY_FIELDS
from repro.core.policy import KNOB_S, DitherCtx, DitherPolicy
from repro.kernels import ops
from repro.launch.program import merge_legacy_flags
from repro.models.api import ssm_model
from repro.models.mamba import SSMConfig, SSMLMConfig
from repro.obs.bus import MetricsBus, get_bus, set_bus
from repro.obs.trace import profile_span, step_span
from repro.optim import OptConfig, init_opt_state
from repro.train import Trainer, TrainerConfig

BATCH, SEQ, BLOCK = 2, 64, 128
SSM = SSMConfig(d_model=64, d_inner=128, head_dim=32, d_state=16, chunk=16)
LAYERS = 2

# every scope the step program names, as a path of name-stack components
SCOPES = ("step/grad", "step/update", "embed", "layers", "block/norm",
          "mixer/in_proj", "mixer/conv", "mixer/ssd", "mixer/out_proj", "head",
          "dither/bwd", "dither/bwd/noise", "dither/bwd/nsd",
          "dither/bwd/pack", "dither/bwd/matmul", "dither/bwd/tally")


def tiny_model(remat=True):
    return ssm_model(SSMLMConfig(name="tiny", n_layers=LAYERS, vocab=256,
                                 ssm=SSM, dtype=jnp.float32, remat=remat))


def kernel_program(collect_stats=False):
    base = DitherPolicy(variant="paper", s=2.0, collect_stats=collect_stats)
    return merge_legacy_flags(
        "dither: rule L.ssm.*:variant=kernel").dither_program(base)


def batches(batch=BATCH):
    key = jax.random.PRNGKey(7)
    while True:
        key, sub = jax.random.split(key)
        t = jax.random.randint(sub, (batch, SEQ + 1), 0, 256)
        yield {"tokens": t[:, :-1], "labels": t[:, 1:]}


def trainer(model, policy, steps=1, grad_accum=1):
    return Trainer(model, OptConfig(lr=1e-3),
                   TrainerConfig(total_steps=steps, grad_accum=grad_accum,
                                 log_every=0), policy=policy)


def tiles(rows, cols):
    return -(-rows // BLOCK) * -(-cols // BLOCK)


def scope_path(op_name: str) -> str:
    """A name stack without its transform wrappers: ``jit(_step)/step/grad/
    transpose(jvp(head))/mul`` -> ``_step/step/grad/head/mul``."""
    return re.sub(r"[\w.\-]+\(|\)", "", op_name)


def holds(op_name: str, scope: str) -> bool:
    parts = [p for p in scope_path(op_name).split("/") if p]
    want = scope.split("/")
    return any(parts[i:i + len(want)] == want
               for i in range(len(parts) - len(want) + 1))


@pytest.fixture
def bus():
    old = get_bus()
    b = set_bus(MetricsBus())
    yield b
    set_bus(old)


# ---------------------------------------------------------------------------
# named scopes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def step_op_names():
    model = tiny_model()
    tr = trainer(model, kernel_program())
    params, _ = model.init(jax.random.PRNGKey(0))
    opt_state = init_opt_state(params, tr.opt_cfg)
    hlo = tr.lower_step(params, opt_state, next(batches())).as_text(
        dialect="hlo", debug_info=True)
    return [n for names in re.findall(r'op_name="([^"]*)"', hlo)
            for n in names.split(";")]


@pytest.mark.parametrize("scope", SCOPES)
def test_step_hlo_names_scope(step_op_names, scope):
    assert any(holds(n, scope) for n in step_op_names), scope


def test_scope_path_unwraps_transforms():
    name = "jit(_step)/step/grad/transpose(step/grad)/jvp(head)/dither/bwd/x"
    assert scope_path(name) == "_step/step/grad/step/grad/head/dither/bwd/x"
    assert holds(name, "head") and holds(name, "dither/bwd")
    assert not holds(name, "bwd/dither")
    assert not holds("jit(_step)/step/grad/jvp(header)/mul", "head")


# ---------------------------------------------------------------------------
# the tally
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grad_accum", [1, 2])
def test_tiles_are_the_padded_grids(bus, grad_accum):
    """Every kernel-variant projection counts its padded tile grid once, in
    each layer and each micro-batch."""
    batch = 4
    tr = trainer(tiny_model(), kernel_program(), grad_accum=grad_accum)
    out = tr.fit(batches(batch))
    m = out["metrics"]
    rows = batch // grad_accum * SEQ
    grid = tiles(rows, SSM.d_in_proj) + tiles(rows, SSM.d_model)
    assert float(m["dither_tiles"]) == grad_accum * LAYERS * grid
    assert float(m["dither_elements"]) == (
        grad_accum * LAYERS * rows * (SSM.d_in_proj + SSM.d_model))
    assert 0 < float(m["dither_tiles_live"]) <= float(m["dither_tiles"])
    assert 0 < float(m["dither_zeros"]) < float(m["dither_elements"])


@pytest.fixture(scope="module")
def counted_step():
    """One step with dither telemetry on, each kernel backward's cotangent,
    key and s sent to the host beside it."""
    seen = []
    kernel_bwd = dithered._dense_kernel_bwd

    def recording_bwd(x, w, key, knobs, spec, name, g):
        jax.debug.callback(
            lambda g, k, s: seen.append((np.asarray(g), np.asarray(k),
                                         float(s))),
            g.reshape(-1, g.shape[-1]), key, knobs[KNOB_S])
        return kernel_bwd(x, w, key, knobs, spec, name, g)

    old = get_bus()
    b = set_bus(MetricsBus())
    dithered._dense_kernel_bwd = recording_bwd
    try:
        out = trainer(tiny_model(remat=False),
                      kernel_program(collect_stats=True)).fit(batches())
        jax.block_until_ready(out["metrics"])
        jax.effects_barrier()
        rows = {t: b.rows("dither", t) for t in b.tags("dither")}
        tally = b.rows("tally", "train")
    finally:
        dithered._dense_kernel_bwd = kernel_bwd
        set_bus(old)
    return out["metrics"], seen, rows, tally


def test_tiles_live_equal_recomputed_masks(counted_step):
    metrics, seen, _, _ = counted_step
    assert len(seen) == 2 * LAYERS
    live = sum(int(jnp.sum(ops.quantize_and_mask(
        jnp.asarray(g), jnp.asarray(k), s).mask)) for g, k, s in seen)
    assert float(metrics["dither_tiles_live"]) == live


def test_zero_share_is_the_element_weighted_sparsity(counted_step):
    metrics, _, rows, _ = counted_step
    sizes = {"L.ssm.in": BATCH * SEQ * SSM.d_in_proj,
             "L.ssm.out": BATCH * SEQ * SSM.d_model}
    assert all(len(rows[t]) == LAYERS for t in sizes)
    zeros = sum(float(np.sum(rows[t][:, 0])) * n for t, n in sizes.items())
    elements = LAYERS * sum(sizes.values())
    share = float(metrics["dither_zeros"]) / float(metrics["dither_elements"])
    assert float(metrics["dither_elements"]) == elements
    assert share == pytest.approx(zeros / elements, rel=1e-6)


def test_fit_records_the_last_tally_on_the_bus(counted_step):
    metrics, _, _, tally = counted_step
    assert all(isinstance(metrics[f"dither_{k}"], jax.Array)
               for k in TALLY_FIELDS)
    np.testing.assert_array_equal(
        tally, [[float(metrics[f"dither_{k}"]) for k in TALLY_FIELDS]])


def test_tally_leaves_loss_and_grads_bit_identical():
    model = tiny_model()
    params, _ = model.init(jax.random.PRNGKey(0))
    batch = next(batches())
    program = kernel_program()
    ctx = DitherCtx.for_step(jax.random.PRNGKey(3), 0,
                             program.phase_policy_at(0), program=program)

    def loss(p, t):
        return model.loss(p, batch, ctx=dataclasses.replace(ctx, tally=t))

    tally = {name: jnp.zeros((len(TALLY_FIELDS),), jnp.float32)
             for name in ("L.ssm.in", "L.ssm.out", "lm_head")}
    l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss(p, None)))(params)
    l1, (g1, counts) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
        params, tally)
    assert np.asarray(l0).tobytes() == np.asarray(l1).tobytes()
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # each layer name counts its own projections, summed over the scan;
    # the paper-variant head counts nothing
    rows = BATCH * SEQ
    assert float(counts["L.ssm.in"][1]) == LAYERS * tiles(rows,
                                                          SSM.d_in_proj)
    assert float(counts["L.ssm.out"][1]) == LAYERS * tiles(rows, SSM.d_model)
    assert float(counts["L.ssm.in"][3]) == LAYERS * rows * SSM.d_in_proj
    assert not np.any(np.asarray(counts["lm_head"]))


def test_plain_step_has_no_tally(bus):
    out = trainer(tiny_model(), None).fit(batches())
    assert not any(k.startswith("dither_") for k in out["metrics"])
    assert bus.row_count("tally", "train") == 0


# ---------------------------------------------------------------------------
# the trainer's spans
# ---------------------------------------------------------------------------

def test_fit_spans_reach_the_profiler_without_obs(bus):
    tr = trainer(tiny_model(), None)
    feed = batches()
    out = tr.fit(feed)  # compile outside the trace
    tr.tcfg.total_steps = 3
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out = tr.fit(feed, out["params"], out["opt_state"])
            jax.block_until_ready(out["params"])
        finally:
            jax.profiler.stop_trace()
        (pb,) = Path(d).rglob("*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(str(pb))
    names, steps = [], []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                names.append(ev.name)
                if ev.name == "train":
                    steps.append(dict(ev.stats)["step_num"])
    assert sorted(steps) == [1, 2]
    for span in ("data", "dispatch", "controller"):
        assert names.count(span) == 2, span
    # without a run observer the spans write nothing to the bus
    assert bus.row_count("phase", "dispatch") == 0


def test_profile_spans_cost_under_50us_a_step():
    """The five span enters and exits of a step (step, data, dispatch,
    controller, and one spare) cost well under 50 us of host time."""
    def one_step(i):
        with step_span(i):
            for name in ("data", "dispatch", "controller", "checkpoint"):
                with profile_span(name):
                    pass

    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for i in range(2000):
            one_step(i)
        best = min(best, (time.perf_counter() - t) / 2000)
    assert best < 50e-6, best


def test_bus_keeps_device_rows_until_read(bus):
    row = jnp.arange(4, dtype=jnp.float32)
    bus.record("tally", "t", row)
    bus.record("tally", "t", np.ones(4))
    np.testing.assert_array_equal(bus.rows("tally", "t"),
                                  [[0, 1, 2, 3], [1, 1, 1, 1]])
    with pytest.raises(ValueError):
        bus.record("tally", "t", jnp.zeros(3))
