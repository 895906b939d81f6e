"""Step-phase tracing: host-side spans that land on the metrics bus.

``with span("dispatch"): ...`` measures the wall-clock of one phase of one
step and records a row on the ``"phase"`` stream, tagged by the span *path*
(nested spans join with ``/``: ``"dispatch/compile"``). Each span also
opens a ``jax.profiler.TraceAnnotation`` so the same phase shows up in XLA
profiler timelines under the same name — one taxonomy for host timing and
device profiles.

The span taxonomy used by the built-in drivers:

* ``data``        — batch construction / next(loader)
* ``dispatch``    — the jitted step call (async dispatch + any host sync
                    the caller performs inside)
* ``controller``  — the sparsity-controller host tick (includes the
                    effects-barrier telemetry drain)
* ``checkpoint``  — checkpoint save/wait (train-loop side)
* ``ckpt_gather`` / ``ckpt_drain`` / ``ckpt_wait`` — checkpoint
                    device->host transfer / backpressure join / final join
* ``ckpt_write``  — the async writer thread's disk work, with nested
                    ``serialize`` / ``commit`` / ``rotate`` phases (its own
                    root path: span stacks are thread-local)
* ``monitor``     — health-monitor evaluation (repro.obs.monitor)
* ``admit`` / ``decode`` — serving-engine tick phases
* ``lower`` / ``compile`` — dry-run cell phases

``Trainer.fit`` opens its ``data`` / ``dispatch`` / ``controller`` /
``checkpoint`` spans on every step: through the run observer's tracer when a
``RunObs`` is attached, else through :func:`profile_span`, which opens the
profiler annotation alone (no bus row, no host sync), so the spans land on
the profiler's clock in every run. Each step also runs inside
:func:`step_span` (a ``jax.profiler.StepTraceAnnotation`` named ``train``
with the step number).

Inside *jitted* code host spans cannot run; use :func:`annotate` (a thin
``jax.named_scope``) there, which names the HLO region: each device op of a
profile carries its name stack in the ``tf_op`` stat of its event metadata,
so device time is attributed to the same taxonomy. The scopes in the step
program:

* ``step/grad`` / ``step/comm`` / ``step/update`` — ``Trainer._step``
* ``embed``, ``layers``, ``block/norm``, ``mixer/in_proj``,
  ``mixer/conv``, ``mixer/ssd``, ``mixer/out_proj``, ``head`` — the
  Mamba-2 model (``repro.models.mamba``)
* ``dither/bwd`` — every dithered backward rule (``repro.core.dithered``),
  with ``noise``, ``nsd``, ``pack``, ``matmul`` and ``tally`` inside it
  (``repro.kernels.ops`` for the kernel path)

A scope is HLO metadata: it changes no op, and JAX's persistent
compilation cache ignores it unless :func:`keep_scopes_in_compile_cache`
puts it in the key, as the ``Trainer`` does.

The module-level :func:`span` uses the process-default tracer, whose step
counter the training/serving loops advance with :func:`set_step`.
Recording is cheap (a perf_counter pair and a list append) and always on;
whether the rows go anywhere durable is the run-log exporter's decision.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Iterator, Optional

import numpy as np

from repro.obs.bus import MetricsBus, get_bus
from repro.obs.streams import PHASE

_TLS = threading.local()


def annotate(name: str):
    """Named scope for *traced* code: spans inside jit land in the HLO /
    device profile under the same taxonomy as the host spans."""
    import jax

    return jax.named_scope(name)


class Tracer:
    """Span recorder bound to a bus; one per process is typical."""

    def __init__(self, bus: Optional[MetricsBus] = None):
        self._bus = bus
        self._step = 0

    @property
    def bus(self) -> MetricsBus:
        return self._bus if self._bus is not None else get_bus()

    def set_step(self, step: int) -> None:
        """Advance the step index stamped on subsequent span rows."""
        self._step = int(step)

    @property
    def step(self) -> int:
        return self._step

    def _stack(self) -> list:
        stack = getattr(_TLS, "span_stack", None)
        if stack is None:
            stack = _TLS.span_stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        """Measure one phase; nested spans record under a joined path."""
        import jax

        stack = self._stack()
        stack.append(name)
        path = "/".join(stack)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            self.bus.record(PHASE.name, path,
                            np.array([self._step, dt], np.float32))


_DEFAULT = Tracer()


def get_tracer() -> Tracer:
    return _DEFAULT


def span(name: str):
    """``with span("data"): ...`` on the process-default tracer."""
    return _DEFAULT.span(name)


def set_step(step: int) -> None:
    _DEFAULT.set_step(step)


def keep_scopes_in_compile_cache() -> None:
    """Key JAX's persistent compilation cache on the programs' metadata.

    By default the key strips the name stacks, so a program that differs
    from a cached one only in its scopes is handed the cached executable,
    and a profile of it shows the other program's names. Programs whose
    device time is attributed by scope turn this on.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def profile_span(name: str):
    """A span on the profiler's clock alone: a ``TraceAnnotation`` with no
    bus row and no timing, for loops that run without a run observer."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def step_span(step: int, name: str = "train"):
    """Marks one step of a loop in the profile (its step number too)."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)
