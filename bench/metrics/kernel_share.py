"""Share of the sweep program's device busy time spent in Mosaic kernels
(trace events of ``tpu_custom_call``, whatever their names); the rest is
XLA glue: mask expansion, neighbour gathers, MSE reductions, copies."""
LAYER = "kernels: kernels/gossip_round.py, kernels/segment_round.py"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy", "sensor_field.static"]


def read(ctx):
    if ctx.trace is None or ctx.trace.scan_busy_s <= 0:
        return None
    return 100.0 * ctx.trace.kernel_s / ctx.trace.scan_busy_s
