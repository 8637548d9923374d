"""Share of the chip's roofline the sweep program reached: the least time
the chip could take for the window's rounds (``bench.work``: the larger of
their flops over peak compute and their bytes over peak HBM bandwidth), over
the program's device busy time in the trace."""
import sys

LAYER = "scan: sweep.engine._sweep_scan"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy", "sensor_field.static"]


def read(ctx):
    from bench import work

    if ctx.trace is None or ctx.trace.scan_busy_s <= 0 or ctx.peaks is None:
        return None
    least, bound = work.roofline(ctx.flops, ctx.bytes, ctx.peaks)
    print(f"roofline.round: {bound}-bound; {ctx.flops:.6e} flops, {ctx.bytes:.6e} "
          f"bytes, least {least:.6e} s over {ctx.trace.scan_busy_s:.6e} s busy",
          file=sys.stderr)
    return 100.0 * least / ctx.trace.scan_busy_s
