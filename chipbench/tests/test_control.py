"""The comparison that decides ``correct`` against the control and planted
faults, at CPU sizes (``conftest.SIZES``), with the cells' own limits.

The int8 control is held here for the plain cell only. In the dither cell
it separates from sound runs only at the chip's size (48 layers of width
1024: ``update_norm_gap`` 0.145-0.164 on three seeds against a limit of
0.045, PERF.md); at a CPU size its error stays within two dither draws'.
"""
import jax
import jax.numpy as jnp
import pytest

from chipbench import check, control, run
from chipbench.tests.conftest import SIZES, small_cell

SEED = 2**31 + 3


def small_run(cell):
    config, wl = small_cell(cell)
    c = run.Cell(cell, SEED, 0.3, False, {"chips": 1}, config, wl)
    return run.run_cell(c, jax.devices(), "cpu")


@pytest.mark.parametrize("cell", list(SIZES))
def test_sound_run_is_correct(cell):
    line = small_run(cell)
    assert line["correct"], line["checks"]
    # the harness read every end-to-end metric from the driver's readings
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_int8_control_fails():
    config, wl = small_cell("mamba2-370m.plain")
    numbers = control.readings(config, wl, seed=SEED)["int8"]
    correct, _ = check.judge(numbers, wl["limits"])
    assert not correct


def unchanged_state(params, grads, state, cfg):
    """A step that returns its parameters and optimizer state unchanged."""
    return params, dict(state, step=state["step"] + 1), {}


def half_batch_loss(loss_fn):
    def loss(params, cfg, batch, **kw):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return loss_fn(params, cfg, half, **kw)
    return loss


@pytest.mark.parametrize("cell", list(SIZES))
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_planted_fault_fails(cell, fault, monkeypatch):
    from repro.models import mamba
    from repro.train import trainer

    if fault == "unchanged_state":
        monkeypatch.setattr(trainer, "apply_updates", unchanged_state)
    else:
        monkeypatch.setattr(mamba, "loss_fn", half_batch_loss(mamba.loss_fn))
    line = small_run(cell)
    assert not line["correct"], line["checks"]


def test_int8_contract_quantizes_forward_and_backward():
    c = control.int8_contract(lambda s, a, b: jnp.einsum(s, a, b))
    a = jnp.linspace(-1.0, 1.0, 12).reshape(3, 4)
    b = jnp.eye(4)
    y = c("ij,jk->ik", a, b)
    assert jnp.allclose(y, control.quantize(a))
    g = jax.grad(lambda a: jnp.sum(c("ij,jk->ik", a, b) * a))(a)
    assert jnp.all(jnp.isfinite(g))
