"""Device microseconds per round of the sweep program: the union of its
operations' intervals in the window (profiler trace), over the rounds the
window ran (T x sweeps)."""
LAYER = "scan: sweep.engine._sweep_scan"
UNIT = "us"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy", "sensor_field.static"]


def read(ctx):
    if ctx.trace is None or ctx.trace.scan_busy_s <= 0 or not ctx.sweeps:
        return None
    return ctx.trace.scan_busy_s / (ctx.sweeps * ctx.rounds) * 1e6
