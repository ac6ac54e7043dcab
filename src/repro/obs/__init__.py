"""Unified telemetry: metrics registry, span tracing, Perfetto export.

Everything is **off by default** and safe to leave imported in hot paths:

* metrics — host-side `Registry` of counters/gauges/histograms.
* tracing — `trace.span("name", **args)` context manager / decorator;
  a shared no-op object when disabled, Chrome trace-event ("X") records
  when enabled.  Export with `write_chrome_trace` and open in Perfetto.
  Every span is also a `jax.profiler.TraceAnnotation`, so a profile
  capture shows it on the device's clock (enabled or not).
* PerfReport — Vortex-style derived report (IPC, stall/idle breakdown,
  D-cache hit rate, occupancy) from the SIMT machine's stats dict.
* kernel wrappers — `instrument_kernel` wraps a jitted kernel entry
  point with launch counting + wall timing, gated on
  `enable_kernel_timing()`.

See `src/repro/obs/README.md` for usage, and run
`PYTHONPATH=src python -m repro.obs.demo` for an end-to-end example.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

from repro.obs.export import (event_tree, load_chrome_trace, text_summary,
                              to_openmetrics, write_chrome_trace)
from repro.obs.flight import FlightRecorder, flight, validate_flight
from repro.obs.perf import PerfReport
from repro.obs.registry import Counter, Gauge, Histogram, Registry, metrics
from repro.obs.server import Liveness, ObsServer
from repro.obs.tracing import Tracer, trace, tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "metrics",
    "Tracer", "trace", "tracer",
    "write_chrome_trace", "load_chrome_trace", "event_tree", "text_summary",
    "to_openmetrics",
    "PerfReport",
    "FlightRecorder", "flight", "validate_flight",
    "Liveness", "ObsServer",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "enable_kernel_timing", "disable_kernel_timing",
    "kernel_timing_enabled", "instrument_kernel",
]


# ---------------------------------------------------------------------------
# global switches
# ---------------------------------------------------------------------------

def enable_tracing() -> None:
    tracer.enable()


def disable_tracing() -> None:
    tracer.disable()


def tracing_enabled() -> bool:
    return tracer.enabled


_kernel_timing = False


def enable_kernel_timing() -> None:
    global _kernel_timing
    _kernel_timing = True


def disable_kernel_timing() -> None:
    global _kernel_timing
    _kernel_timing = False


def kernel_timing_enabled() -> bool:
    return _kernel_timing


# ---------------------------------------------------------------------------
# kernel instrumentation
# ---------------------------------------------------------------------------

def instrument_kernel(name: str, jit_fn, registry: Optional[Registry] = None):
    """Wrap a jitted kernel entry point with optional launch counting and
    wall timing.

    Disabled (default): one module-global bool check, then straight into
    the jitted function — no counters, no clock reads, and crucially no
    change to the jitted callee, so the `jax.jit` cache behaves exactly
    as without instrumentation.

    Enabled: bumps ``kernels.<name>.launches`` and, when the call is a
    real device execution (arguments are concrete, not tracers — i.e. the
    kernel is not being traced into an enclosing jit), blocks on the
    result and records ``kernels.<name>.time_s``.  Calls made during an
    outer trace count as launches but are not timed, since the actual
    execution happens inside the enclosing computation.
    """
    import jax

    @functools.wraps(jit_fn)
    def wrapped(*args, **kwargs):
        if not _kernel_timing:
            return jit_fn(*args, **kwargs)
        reg = registry if registry is not None else metrics
        reg.counter(f"kernels.{name}.launches").inc()
        traced = any(isinstance(x, jax.core.Tracer)
                     for x in jax.tree.leaves((args, kwargs)))
        if traced:
            return jit_fn(*args, **kwargs)
        with trace.span(f"kernel:{name}"):
            t0 = time.perf_counter()
            out = jit_fn(*args, **kwargs)
            out = jax.block_until_ready(out)
            reg.histogram(f"kernels.{name}.time_s").observe(
                time.perf_counter() - t0)
        return out

    return wrapped
