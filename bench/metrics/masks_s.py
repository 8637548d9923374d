"""Host seconds per sweep in ``repro.sweep.build_round_masks``: the link
schedule sampled for every cell and round, timed by the harness's span
around the call."""
LAYER = "host: sweep.grid.build_round_masks"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy"]


def read(ctx):
    if all(d == "static" for d in ctx.cell["dynamics"]) or not ctx.masks_s:
        return None
    return sum(ctx.masks_s) / len(ctx.masks_s)
