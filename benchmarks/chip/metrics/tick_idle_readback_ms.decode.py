"""Device idle time per engine tick spent in the engine's readback of a
step's results, in ms: for each `serving.tick` span inside the traced
window, the time within its `*.readback` spans in which no device
operation ran, as a mean over the ticks (and the chips).  Computed with
`tick_idle_host_ms.decode`, which reads the rest of the idle time in
the ticks; None where the trace has no `serving.tick` span or no device
plane."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    from benchmarks.chip import bench
    out = bench.load_module("metrics/tick_idle_host_ms.decode.py").split(tr)
    return None if out is None else out[1]
