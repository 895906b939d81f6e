"""Records ``chipbench/traces/steps.xplane.pb`` on one TPU chip.

    python3 chipbench/traces/record_steps.py

Two steps of the program's ``Trainer`` on a 2-layer Mamba-2 at tiny widths,
dithered as the benchmark's dither cell is (``L.ssm.*`` on the kernel
variant, the head on the paper variant), inside a ``window`` span. The
steps compile before the trace starts, so the trace holds what a window
holds: the step program's ops with their named scopes (``tf_op``) and the
trainer's host spans. The trace lies in a directory of its own, since a
reader takes the newest trace under a directory (``chipbench/testdata``
holds another). The Python tracer is off, which keeps the file small.
"""
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.policy import DitherPolicy  # noqa: E402
from repro.launch.program import merge_legacy_flags  # noqa: E402
from repro.models.api import ssm_model  # noqa: E402
from repro.models.mamba import SSMConfig, SSMLMConfig  # noqa: E402
from repro.optim import OptConfig  # noqa: E402
from repro.train import Trainer, TrainerConfig  # noqa: E402

STEPS = 2


def batches():
    key = jax.random.PRNGKey(0)
    while True:
        key, sub = jax.random.split(key)
        t = jax.random.randint(sub, (2, 257), 0, 512)
        yield {"tokens": t[:, :-1], "labels": t[:, 1:]}


def main() -> None:
    assert jax.devices()[0].platform == "tpu", "records on a TPU only"
    model = ssm_model(SSMLMConfig(
        name="tiny", n_layers=2, vocab=512,
        ssm=SSMConfig(d_model=128, d_inner=256, head_dim=64, d_state=32,
                      chunk=64), dtype=jnp.bfloat16, remat=True))
    policy = merge_legacy_flags(
        "dither: rule L.ssm.*:variant=kernel").dither_program(
            DitherPolicy(variant="paper", s=2.0))
    trainer = Trainer(model, OptConfig(lr=1e-3),
                      TrainerConfig(total_steps=1, log_every=0),
                      policy=policy)
    feed = batches()
    out = trainer.fit(feed)
    jax.block_until_ready(out["params"])
    trainer.tcfg.total_steps = 1 + STEPS
    tmp = tempfile.mkdtemp()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            out = trainer.fit(feed, out["params"], out["opt_state"])
            jax.block_until_ready(out["params"])
        jax.profiler.stop_trace()
        (pb,) = Path(tmp).rglob("*.xplane.pb")
        shutil.copy(pb, Path(__file__).with_name("steps.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
