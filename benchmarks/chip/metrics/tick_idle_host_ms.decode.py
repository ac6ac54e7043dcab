"""Device idle time per engine tick that the engine's own host code
causes, in ms: for each `serving.tick` span (the engine's step
annotation) inside the traced window, the time within it in which no
device operation ran and no `*.readback` span was open, as a mean over
the ticks (and the chips).  `tick_idle_readback_ms.decode` reads the
idle time inside the readback spans; the two add up to the device idle
time within ticks.

Device idle is the complement of the union of the device's operations
(trace_reduce.union), as `device_idle_share` reads it.  None where the
trace has no `serving.tick` span or no device plane."""

TICK = "serving.tick"
READBACK = ".readback"


def _intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(spans):
    return sum(t - s for s, t in spans)


def split(tr):
    """(host-code idle, readback idle) in ms per tick, or None."""
    from benchmarks.chip import trace_reduce
    lo, hi = tr.window()
    host = [e for evs in tr.host.values() for e in evs]
    # a step annotation's step_num is a stat, or in the name after '#'
    ticks = [e for e in host if e.name.split("#")[0] == TICK
             and e.start >= lo and e.end <= hi]
    planes = trace_reduce.device_planes(tr)
    if not ticks or not planes:
        return None
    in_ticks = trace_reduce.union(ticks)
    reads = _intersect(trace_reduce.union(
        e for e in host if e.name.endswith(READBACK)), in_ticks)
    idle = read_idle = 0.0
    for plane in planes:
        busy = trace_reduce.union(trace_reduce.device_events(tr, plane))
        idle += _length(in_ticks) - _length(_intersect(busy, in_ticks))
        read_idle += _length(reads) - _length(_intersect(busy, reads))
    n = len(ticks) * len(planes) * 1e6
    return (idle - read_idle) / n, read_idle / n


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    out = split(tr)
    return None if out is None else out[0]
