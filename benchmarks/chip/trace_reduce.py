"""Reduce a `jax.profiler` trace (an .xplane.pb) to the numbers the
per-layer metrics read: device busy time, device time per program, the
operations that took the most time, and the idle gaps labelled by what
the host was doing.

A device plane is one named `/device:<kind>:<n>`.  Its `XLA Ops` line
holds one event per operation run (fusions, copies, collectives); its
`XLA Modules` line one event per program run, named after the jitted
function (`jit__decode_step(...)`).  The host plane `/host:CPU` holds
the spans of `jax.profiler.TraceAnnotation` on the thread that opened
them.  Both are on one clock in the reduced trace.

The traced window is the host span named `window` (the harness opens
it around what it traces); busy time, gaps and program times are
clipped to it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"     # bench.WINDOW_SPAN


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns
    end: float              # ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> operations
    modules: Dict[str, List[Event]]    # device plane -> program runs
    host: Dict[str, List[Event]]       # host thread -> spans

    def window(self) -> Tuple[float, float]:
        spans = [e for evs in self.host.values() for e in evs
                 if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no host span {WINDOW_SPAN!r}")
        return min(e.start for e in spans), max(e.end for e in spans)


def from_profile(pd) -> Trace:
    """From a `jax.profiler.ProfileData`."""
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = ops if line.name == OPS_LINE else modules
                    dst.setdefault(plane.name, []).extend(_events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.setdefault(line.name, []).extend(_events(line))
    return Trace(ops, modules, host)


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def load(trace_dir: str) -> Trace:
    """The newest .xplane.pb under a `jax.profiler.start_trace` dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


# ------------------------------------------------------------ reductions

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged intervals covered by at least one event."""
    spans = sorted((e.start, e.end) for e in events if e.end > e.start)
    out: List[Tuple[float, float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def device_events(trace: Trace, plane: str) -> List[Event]:
    """The plane's operations, or its program runs where the trace holds
    no operations (a trace mode that records programs only)."""
    return trace.ops.get(plane) or trace.modules.get(plane, [])


def busy_ns(trace: Trace, plane: str) -> float:
    lo, hi = trace.window()
    return sum(t - s for s, t in union(clip(device_events(trace, plane),
                                            lo, hi)))


def device_planes(trace: Trace) -> List[str]:
    return sorted(set(trace.ops) | set(trace.modules))


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an operation ran on the device,
    averaged over the device planes."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    return sum(busy_ns(trace, p) for p in planes) / len(planes) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, in [0, 1]; None where no device op ran."""
    b = busy_s(trace)
    if b <= 0:
        return None
    return 1.0 - b / window_s(trace)


def program_runs(trace: Trace, program: str) -> List[Event]:
    """Runs of the jitted function `program` on any device in the window
    (module events are named `jit_<fn>` or `jit_<fn>(<id>)`)."""
    pat = re.compile(rf"^jit_{re.escape(program)}(\(.*\))?$")
    lo, hi = trace.window()
    out = []
    for evs in trace.modules.values():
        out.extend(e for e in evs if pat.match(e.name)
                   and e.start >= lo and e.end <= hi)
    return out


def program_time(trace: Trace, program: str) -> Tuple[float, int]:
    """(device seconds, runs) of one jitted function in the window."""
    runs = program_runs(trace, program)
    return sum(e.end - e.start for e in runs) / 1e9, len(runs)


OP_TEXT = re.compile(r"^%?([\w.\-]+) = (\(?[\w]+\[[\d,]*\])")


def op_label(name: str) -> str:
    """An operation's HLO name and the type of its (first) result, from
    the full instruction text the TPU trace gives as an op's name:
    `%fusion.134 = bf16[8,1024,32,96]{...} fusion(...)` ->
    `fusion.134 bf16[8,1024,32,96]`."""
    m = OP_TEXT.match(name)
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m else name[:120]


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The device operations that took most time in the window (by
    `op_label`), in seconds summed over the device planes."""
    lo, hi = trace.window()
    tot: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        for e in clip(evs, lo, hi):
            tot[op_label(e.name)] += (e.end - e.start) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def _host_label(host: Dict[str, List[Event]], t: float,
                skip: Sequence[str] = (WINDOW_SPAN,)) -> str:
    """What the host was doing at time t: the outermost span open then on
    the thread that has the most spans open, and the innermost, joined by
    ' > ' ('host idle' where no thread has one open)."""
    best: List[Event] = []
    for evs in host.values():
        cover = sorted((e for e in evs if e.start <= t < e.end
                        and e.name not in skip), key=lambda e: e.start)
        if len(cover) > len(best):
            best = cover
    if not best:
        return "host idle"
    names = [best[0].name] + ([best[-1].name] if len(best) > 1 else [])
    return " > ".join(names)


SHORT_GAP_NS = 50_000
SHORT_GAP = "between ops (gaps under 50 us)"


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device idle time in the window, summed by what the host was doing
    at each gap's midpoint (gaps under SHORT_GAP_NS, the device's own
    issue latency between operations, in one entry); the n largest, in
    seconds averaged over the device planes."""
    lo, hi = trace.window()
    planes = device_planes(trace)
    tot: Dict[str, float] = defaultdict(float)
    for plane in planes:
        cur = lo
        for s, t in union(clip(device_events(trace, plane), lo, hi)) + [
                (hi, hi)]:
            if s > cur:
                label = (SHORT_GAP if s - cur < SHORT_GAP_NS else
                         _host_label(trace.host, (cur + s) / 2))
                tot[label] += (s - cur) / 1e9 / len(planes)
            cur = max(cur, t)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def breakdown(trace: Trace) -> Dict[str, List[List]]:
    return {"device_ops": [[k, v] for k, v in top_ops(trace)],
            "idle_gaps": [[k, v] for k, v in idle_gaps(trace)]}
