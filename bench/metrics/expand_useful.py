"""Share of the edge slots the in-scan dense mask expansion contracts over
that hold real edges: the sweep engine's ``expand_real`` over
``expand_slots`` (real edges x rounds against G E_pad T, over the dense
dynamic batches). The counters run from import, warm-up sweep included: the
share holds for a cell whose sweeps all have one shape. None where the
program has no such counters or ran no dense dynamic batch (the static
cell)."""
LAYER = "scan: sweep.engine._sweep_scan"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy"]


def read(ctx):
    from repro.sweep import engine

    counters = getattr(engine, "counters", None)
    c = counters() if counters is not None else {}
    if not c.get("expand_slots"):
        return None
    return 100.0 * c["expand_real"] / c["expand_slots"]
