"""Megabytes a sweep moves between host and device: the sweep engine's
``bytes_in`` (every array ``run_batch`` puts on the device, the link bits
included) and ``bytes_out`` (the outputs it fetches), over its ``batches``
(one ``run_batch`` call per sweep here). The counters run from import, so
the warm-up sweep is in the sum: the number holds for a cell whose sweeps
all have one shape, as both cells' do, and would blend shapes otherwise
(until ``Context`` carries the counters' change over the window). None
where the program has no counters."""
LAYER = "host: sweep.engine.run_batch"
UNIT = "MB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy", "sensor_field.static"]


def read(ctx):
    from repro.sweep import engine

    counters = getattr(engine, "counters", None)
    c = counters() if counters is not None else {}
    if not c.get("batches"):
        return None
    return (c["bytes_in"] + c["bytes_out"]) / c["batches"] / 1e6
