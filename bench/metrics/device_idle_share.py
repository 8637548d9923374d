"""Share of the timed window in which no operation ran on the device:
1 - (union of device operation intervals) / window, from the trace."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy", "sensor_field.static"]


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
