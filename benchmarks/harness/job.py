"""What a job (``train``, ``serve_open``, ``serve_backlog``) is given and
what it hands back."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import time


@dataclasses.dataclass
class Context:
    """One run of one cell."""
    root: str               # the checkout
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float          # time.perf_counter() when the process started
    setup: dict = dataclasses.field(default_factory=dict)
    _last: float = 0.0

    def __post_init__(self):
        self._last = self.t_start

    def lap(self, phase):
        """Bill the time since the last lap to ``phase`` of set-up."""
        now = time.perf_counter()
        self.setup[phase] = self.setup.get(phase, 0.0) + now - self._last
        self._last = now

    @property
    def trace_dir(self):
        return os.path.join(self.root, ".bench_trace")


@dataclasses.dataclass
class Run:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict                    # metric name -> value
    samples: dict                       # name -> list of numbers
    facts: dict                         # sizes the readers need
    trace: object = None                # harness.trace.Trace
    notes: list = dataclasses.field(default_factory=list)


def load_module(root, relpath, name):
    """A Python file of the checkout, by path."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Counts the programs JAX compiles, or loads from its cache, from
    :meth:`arm` on.  Inside the measured window the count must stay 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1

    def arm(self):
        self.armed = True
