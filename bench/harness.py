"""What every driver shares: the run's context, the traced window, the
device's peak memory, and the program pieces named by a configuration.

A driver (``bench/drivers/<driver>.py``, named by the configuration's
``driver`` key) builds the system under test from the configuration,
runs the cell's traffic through it for one window, and returns a
``Run``: the end-to-end readings, the counts attempted and failed, the
numbers compared for ``correct`` beside their limits, and a ``record``
that the per-layer readers (``bench/metrics/<name>.py``) read from.
"""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    #: time.perf_counter() at process start (set-up counts from here)
    t_start: float
    peaks: Optional[dict] = None
    compiles: Optional["CompileCounter"] = None
    #: also read the control's numbers (``bench/control.py`` only): the
    #: reference computed in bfloat16 put in the sampler's place at the
    #: same positions
    control: bool = False

    def sub_seed(self, stream: str) -> int:
        """A 31-bit seed for ``stream``, derived from the run's seed."""
        ss = np.random.SeedSequence([int(self.seed),
                                     int.from_bytes(stream.encode()[:8]
                                                    .ljust(8, b"\0"),
                                                    "little")])
        return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Run:
    setup_s: float
    e2e: dict
    attempted: int
    failed: int
    #: name -> (value, limit); correct iff every value <= its limit
    checks: dict
    record: dict
    memory_peak_bytes: int
    device_count: int


def program(spec: dict):
    """The program's walk program for a configuration's program spec."""
    from repro.walks import deepwalk, node2vec, ppr_nibble
    kind = spec["kind"]
    if kind == "node2vec":
        return node2vec(a=spec["a"], b=spec["b"], weighted=spec["weighted"])
    if kind == "deepwalk":
        return deepwalk(weighted=spec["weighted"])
    if kind == "ppr_nibble":
        return ppr_nibble(alpha=spec["alpha"], eps=spec["eps"],
                          weighted=spec["weighted"])
    raise ValueError(f"unknown program kind {kind!r}")


def device_graph(indptr, indices, h):
    """The program's CSR graph, uploaded from the benchmark's arrays."""
    import jax.numpy as jnp
    from repro.graphs.csr import CSRGraph
    return CSRGraph(indptr=jnp.asarray(indptr, jnp.int32),
                    indices=jnp.asarray(indices, jnp.int32),
                    h=jnp.asarray(h, jnp.float32),
                    labels=jnp.zeros(indices.shape, jnp.int32))


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileCounter:
    """Counts XLA backend compiles (JAX's monitoring events) so a run can
    report how many fell inside its window; there should be none."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration


class Window:
    """The measured window: a host span named ``bench.window`` and,
    with tracing on, the profiler around it."""

    def __init__(self, trace: bool, compiles: Optional[CompileCounter] = None):
        self.trace = trace
        self.dir = None
        self.t0 = self.t1 = None
        self.compiles = compiles
        self.compiles_in_window = None

    @contextlib.contextmanager
    def measure(self):
        import jax
        if self.trace:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            # Python function tracing off: it slows the host loop the
            # window measures; TraceAnnotation spans stay on
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                c0 = self.compiles.count if self.compiles else 0
                self.t0 = time.perf_counter()
                yield self
                self.t1 = time.perf_counter()
                if self.compiles:
                    self.compiles_in_window = self.compiles.count - c0
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def reduce(self, kernels: dict, platform: str) -> Optional[dict]:
        """The trace reduced to busy, idle and kernel times (see
        ``trace_reduce``); None without a trace.  The trace files are
        deleted once read."""
        if not self.trace:
            return None
        import trace_reduce
        try:
            return trace_reduce.reduce_dir(self.dir, kernels=kernels,
                                           platform=platform)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
