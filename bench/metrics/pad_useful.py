"""Share of the round-matrix entries the kernels compute that belong to
real nodes: the sweep engine's ``entries_real`` over ``entries_padded``
(dense: the sum of n_g^2 F against G n_pad^2 F_pad). The counters run from
import, warm-up sweep included: the share holds for a cell whose sweeps all
have one shape, as both cells' do (until ``Context`` carries the counters'
change over the window). None where the program has no counters."""
LAYER = "kernels: kernels/gossip_round.py, kernels/segment_round.py"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "sweep_s"
WORKLOADS = ["sensor_field.lossy", "sensor_field.static"]


def read(ctx):
    from repro.sweep import engine

    counters = getattr(engine, "counters", None)
    c = counters() if counters is not None else {}
    if not c.get("entries_padded"):
        return None
    return 100.0 * c["entries_real"] / c["entries_padded"]
