"""What every cell of the chip benchmark shares: finding a cell's files by
name, the device check, the compile meter, percentiles, loading the
per-layer metric readers, and the result line.

Everything a configuration, a traffic mix or a per-layer metric needs is
found by its name in BENCHMARK.json:

    configs/<config>.json     sizes, engine settings, source, cuts
    traffic/<traffic>.json    parameters of the one general generator
    metrics/<metric>.py       a reader: `read(ctx) -> float | None`

A configuration's `kind` ("serving", "simt") names the harness module
that runs it (`<kind>.py` beside this file), and its `family` names the
plain reference (`reference/<family>.py`) and the work functions
(`work/<family>.py`).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(entries: List[Dict[str, Any]], name: str, what: str
         ) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_files(spec: Dict[str, Any], workload: str, root: str = ROOT):
    """-> (workload entry, config dict, traffic dict) for one cell."""
    cell = find(spec["workloads"], workload, "workload")
    centry = find(spec["configs"], cell["config"], "config")
    config = load_json(os.path.join(root, centry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def cell_metrics(spec: Dict[str, Any], workload: str, trace: bool
                 ) -> List[Dict[str, Any]]:
    """The metrics a run of this cell reports: its end-to-end metrics
    (trace 0) or its per-layer metrics (trace 1).  A metric without a
    `workloads` list belongs to every cell that reports what it moves."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def load_module(relpath: str):
    """Import a file under the benchmark's directory by its path (names
    may hold dots, e.g. `metrics/device_idle_share.chat.py`)."""
    path = os.path.join(HERE, relpath)
    if not os.path.exists(path):
        raise FileNotFoundError(f"benchmark file {relpath} is missing")
    name = "chipbench_" + relpath.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    return load_module(f"metrics/{name}.py").read


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


# ----------------------------------------------------------------- device

class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoAccelerator(f"JAX's first device is {info['platform']}, "
                            "not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips; JAX sees "
                            f"{len(devs)}")
    return info


def phase(name: str, t_process: float) -> None:
    """Log the end of one phase of set-up, in seconds from the process's
    start."""
    import sys
    print(f"[setup] {name} done at {time.perf_counter() - t_process:.3f} s",
          file=sys.stderr, flush=True)


def memory_peak_bytes(n_devices: int) -> Optional[int]:
    """The peak of the fullest chip (printed beside its limit)."""
    import sys
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:n_devices]]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if "peak_bytes_in_use" in s]
    if not peaks:
        return None
    limit = max(s.get("bytes_limit", 0) for s in stats)
    print(f"[memory] peak {max(peaks)} bytes in use of {limit}",
          file=sys.stderr, flush=True)
    return max(peaks)


class CompileMeter:
    """Counts JAX's backend compiles (a persistent-cache hit counts its
    retrieval) and their seconds, and the persistent cache's hits."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, every program kept (the default skips those that compile
    in under a second, and those then compile again in every run)."""
    import jax
    path = os.path.join(root, "benchmarks", "chip", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ trace

TRACE_LEAD_S = 2.0
TRACE_SECONDS = 6.0
WINDOW_SPAN = "window"


class TraceWindow:
    """The profiler, on for a part of the window: from TRACE_LEAD_S in
    (a quarter of the window, if shorter) for TRACE_SECONDS or to the
    close (`before_call`; a harness may call `start` and `stop` by a rule
    of its own), switched between two calls of the timed path, with the
    host span `window` (trace_reduce.WINDOW_SPAN) around it.  The Python
    tracer stays off, so the host's cost per call is what it is
    untraced."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.on = False
        self.done = False
        self.dir: Optional[str] = None

    def before_call(self, now: float, t0: float, t_end: float) -> None:
        if not self.on and self.lead_passed(now, t0, t_end):
            self.start()
        elif self.on and (now >= self.t_on + TRACE_SECONDS or now >= t_end):
            self.stop()

    def lead_passed(self, now: float, t0: float, t_end: float) -> bool:
        return now >= t0 + min(TRACE_LEAD_S, (t_end - t0) / 4)

    def start(self) -> None:
        import jax
        if not self.enabled or self.done or self.on:
            return
        import tempfile
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.on, self.t_on = True, time.perf_counter()

    def stop(self) -> None:
        import jax
        if self.on:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on, self.done = False, True


# ------------------------------------------------------------- statistics

def quantile(values: List[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (numpy's default); `inf` entries stand for missing answers."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) or math.isinf(xs[lo]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def seed_words(seed: int, n: int = 2) -> List[int]:
    """n 32-bit words from a seed of any size (a run's seed may exceed 32
    bits)."""
    import numpy as np
    return [int(w) for w in np.random.SeedSequence(seed).generate_state(n)]


# ----------------------------------------------------------------- result

def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                compared: Dict[str, Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The run's last line of standard output.  `compared` comes last:
    each number compared for `correct`, beside its limit."""
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def compared_lines(compared: Dict[str, Dict[str, Any]]) -> List[str]:
    return [f"[compared] {k}: {v['value']} (limit {v['limit']})"
            for k, v in compared.items()]


def limits(workload: str) -> Dict[str, Any]:
    """The limits of a cell's correctness comparison, and the readings
    they were set from (limits/<workload>.json)."""
    return load_json(os.path.join(HERE, "limits", workload + ".json"))
