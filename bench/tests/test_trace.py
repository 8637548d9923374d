"""The reduction from a profiler trace to per-layer numbers."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def op(s, e, name="fusion.1", module="jit__sweep_scan", kernel=False, device="/device:TPU:0"):
    return trace.Op(s, e, name, module, kernel, device)


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.clip([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert trace.length(trace.clip([(0, 10)], [(1, 2), (5, 7)])) == 3


def test_reduce_on_a_made_up_trace():
    spans = [trace.Span(0, 10, "bench.sweep"), trace.Span(0, 2, "bench.masks"),
             trace.Span(2, 10, "bench.run_ensemble"), trace.Span(20, 30, "bench.sweep"),
             trace.Span(20, 30, "bench.run_ensemble")]
    ops = [op(3, 5, "custom-call.2", kernel=True), op(4, 6, "fusion.3"),
           op(6, 7, "copy.1", module="jit_other"), op(12, 14, "fusion.9"),
           op(21, 29, "custom-call.2", kernel=True)]
    r = trace.reduce(ops, spans)
    assert r.window_s == 20                     # the op at 12..14 lies outside
    assert r.busy_s == 4 + 8                    # 3..7 and 21..29
    assert r.scan_busy_s == 3 + 8               # 3..6 and 21..29
    assert r.kernel_s == 2 + 8
    assert r.device_ops[0] == ["custom-call.2", 10]
    assert r.idle_gaps[0] == ["bench.masks", 3]  # 0..3: masks open until 2
    assert ["bench.run_ensemble", 3] in r.idle_gaps
    assert r.spans["bench.sweep"] == [10, 10]


def recorded():
    paths = sorted(DATA.glob("*.xplane.pb"))
    if not paths:
        pytest.fail(f"no recorded chip trace under {DATA}")
    return paths[0]


def test_reduce_on_a_trace_recorded_on_the_chip():
    """Two small lossy dense sweeps (G = 8, N = 64, F = 128, T = 40) and two
    static sparse sweeps (a 4096-node grid) on one TPU v5e, each inside the
    harness's spans."""
    ops, spans = trace.load(str(recorded()))
    assert ops and spans
    assert {o.device for o in ops} == {"/device:TPU:0"}
    r = trace.reduce(ops, spans)
    assert 0 < r.kernel_s < r.scan_busy_s <= r.busy_s < r.window_s
    # what this trace reduced to when it was recorded: a change to the
    # reduction that moves these has to say why
    assert (len(ops), sum(o.kernel for o in ops)) == (3406, 320)
    assert {k: len(v) for k, v in r.spans.items()} == {
        "bench.sweep": 4, "bench.masks": 4, "bench.run_ensemble": 4}
    assert abs(r.window_s - 0.037583139) < 1e-8
    assert abs(r.busy_s - 0.016994902) < 1e-8
    assert abs(r.kernel_s - 0.00203072) < 1e-8
    assert r.device_ops and all(s > 0 for _, s in r.device_ops)
    assert {label for label, _ in r.idle_gaps} <= {
        "bench.sweep", "bench.masks", "bench.run_ensemble", "none"}
    # every device op of the sweep program runs inside a harness span
    window = trace.union((s.start, s.end) for s in spans if s.name == "bench.sweep")
    scan = trace.union((o.start, o.end) for o in ops if trace.SCAN_PROGRAM in o.module)
    assert abs(trace.length(trace.clip(scan, window)) - trace.length(scan)) < 1e-6
