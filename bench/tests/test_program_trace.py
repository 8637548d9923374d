"""The program's spans, scope and counters as the trace reduction reads them."""
from pathlib import Path

import pytest

from bench import program_trace as pt, trace

DATA = Path(__file__).resolve().parent / "data"
FIRST = DATA / "sweeps_v5e.xplane.pb"              # recorded before the program had spans
PROGRAM = DATA / "sweeps_v5e_program.xplane.pb"    # sweeps of the same sizes, with them
CELL = DATA / "sweeps_v5e_lossy_cell.xplane.pb"  # the lossy cell's grid, T = 40
SCOPE = "jit(_sweep_scan)/while/body/closed_call/sweep.expand"
EXPAND = f"{SCOPE}/scatter"


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from (field, value): ints as varints, the rest
    length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _instruction(i, name, op_name="", operands=()):
    packed = b"".join(_varint(o) for o in operands)
    return _msg((1, name), *([(7, _msg((2, op_name)))] if op_name else []),
                (35, i), *([(36, packed)] if packed else []))


def test_scopes_name_instructions_without_metadata_by_their_users():
    """The lossy cell's expansion as the TPU compiler lays it out: a
    sort-based scatter whose sort and fusions carry no metadata, between
    the named index conversion and the named reshape of the result."""
    insts = [
        (1, "convert_fusion", f"{SCOPE}/convert", []),
        (2, "sort.4", "", [1]),
        (3, "get-tuple-element.1", "", [2]),
        (4, "fusion.38", "", [3]),
        (5, "fusion.39", "", [4, 3]),
        (6, "reshape.123", EXPAND, [5]),
        (7, "kernel", "jit(_sweep_scan)/while/body/closed_call/pallas_call", [6]),
        (8, "copy.20", "", [7]),                   # feeds only the loop's tuple
        (9, "copy-start.6", "", []),               # two users that disagree
        (10, "where", f"{SCOPE}/where", [9, 6]),
        (11, "reduce", "jit(_sweep_scan)/while/body/closed_call/reduce_sum", [9, 7]),
        (12, "tuple.1", "", [8, 10, 11]),
    ]
    got = pt.scopes(insts)
    assert {n for n, op in got.items() if "/sweep.expand/" in op} == {
        "convert_fusion", "sort.4", "get-tuple-element.1", "fusion.38", "fusion.39",
        "reshape.123", "where"}
    assert got["copy.20"] == got["copy-start.6"] == got["tuple.1"] == ""
    assert got["kernel"] == insts[6][2]


def test_op_names_from_the_hlo_in_the_trace(tmp_path):
    hlo_stat = 3
    comp = _msg((1, "body"),
                (2, _instruction(1, "p", f"{SCOPE}/slice")),
                (2, _instruction(2, "sort.4", "", [1])),
                (2, _instruction(3, "fusion.38", "", [2])),
                (2, _instruction(4, "reshape.1", EXPAND, [3])),
                (2, _instruction(5, "copy.2", "", [])))
    hlo = _msg((1, _msg((1, "jit__sweep_scan"), (3, comp))))
    event = _msg((1, 77), (2, "jit__sweep_scan(77)"),
                 (5, _msg((1, hlo_stat), (6, hlo))))
    metadata = _msg((2, "/host:metadata"), (4, _msg((1, 77), (2, event))),
                    (5, _msg((1, hlo_stat), (2, _msg((1, hlo_stat), (2, "Hlo Proto"))))))
    device = _msg((2, "/device:TPU:0"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, metadata)))
    names = pt.op_names(str(path))
    assert names[(77, "sort.4")] == names[(77, "fusion.38")] == EXPAND
    assert (77, "copy.2") not in names
    op = trace.Op(0, 1, "fusion.38", "jit__sweep_scan(77)", False, "/device:TPU:0")
    assert pt.op_name(op, names) == EXPAND
    assert pt.op_name(trace.Op(0, 1, "fusion.38", "jit__other(78)", False, "d"), names) == ""


def _made_up():
    spans = [trace.Span(*s) for s in [
        (0, 10, "bench.sweep"), (0, 2, "bench.masks"), (2, 10, "bench.run_ensemble"),
        (0.1, 1.9, "sweep.masks"), (0.5, 1.5, "sweep.masks.draw"),
        (2, 10, "sweep.run_batch"), (2, 4, "sweep.prep"), (4, 9, "sweep.wait"),
        (9, 10, "sweep.fetch"), (20, 30, "sweep.run_batch")]]   # the last outside
    ops = [trace.Op(4, 6, "fusion.3", "jit__sweep_scan(77)", False, "/device:TPU:0"),
           trace.Op(6, 9, "custom-call.1", "jit__sweep_scan(77)", True, "/device:TPU:0")]
    return ops, spans


def test_idle_time_by_innermost_span():
    ops, spans = _made_up()
    idle = pt.idle_by_span(ops, spans)
    assert idle == pytest.approx({
        "bench.masks": 0.2, "sweep.masks": 0.8, "sweep.masks.draw": 1.0,
        "sweep.prep": 2.0, "sweep.fetch": 1.0})
    assert sum(idle.values()) == pytest.approx(10 - 5)


def test_reduce_on_a_made_up_trace():
    ops, spans = _made_up()
    r = pt.reduce(ops, spans, {(77, "fusion.3"): EXPAND})
    assert r.sweeps == 1
    assert r.expand_s == pytest.approx(2)
    assert r.batch_host_s == pytest.approx(8 - 5)
    assert r.masks_draw_s == pytest.approx(1)
    assert r.span_counts["sweep.run_batch"] == 1
    assert pt.reduce(ops, spans, {}).expand_s == 0


def test_a_trace_without_the_program_spans_reads_nothing():
    """The first recorded trace: the program had no spans or scope then."""
    red, prog = pt.load_and_reduce(str(FIRST))
    assert prog.sweeps == 4 and prog.span_counts == {}
    assert prog.expand_s == prog.batch_host_s == prog.masks_draw_s == 0
    # the same reduction as bench.trace's, and its idle time, all named
    assert abs(red.window_s - 0.037583139) < 1e-8
    assert sum(prog.idle.values()) == pytest.approx(red.window_s - red.busy_s)
    assert set(prog.idle) <= {"bench.sweep", "bench.masks", "bench.run_ensemble"}
    assert any(name.startswith("jit(_sweep_scan)/while/body/")
               for name in pt.op_names(str(FIRST)).values())


def test_the_program_spans_and_scope_on_a_trace_recorded_on_the_chip():
    """``record_trace.py`` on one TPU v5e: two lossy dense sweeps (G = 8,
    N = 64, F = 128, T = 40) and two static sparse sweeps (a 4096-node grid),
    each inside the harness's spans, with the program's spans and scope."""
    red, prog = pt.load_and_reduce(str(PROGRAM))
    ops, spans = trace.load(str(PROGRAM))
    # bench.trace reads this trace as it read the first one
    assert {o.device for o in ops} == {"/device:TPU:0"}
    assert 0 < red.kernel_s < red.scan_busy_s <= red.busy_s < red.window_s
    assert {k: len(v) for k, v in red.spans.items()} == {
        "bench.sweep": 4, "bench.masks": 4, "bench.run_ensemble": 4}
    # every phase once a sweep; a draw per lossy cell (4 of the dense 8)
    assert prog.sweeps == 4
    assert prog.span_counts == {
        "sweep.masks": 4, "sweep.masks.draw": 8, "sweep.run_batch": 4,
        "sweep.prep": 4, "sweep.put": 4, "sweep.launch": 4, "sweep.wait": 4,
        "sweep.fetch": 4}
    # what this trace reduced to when it was recorded
    assert (len(ops), sum(o.kernel for o in ops)) == (2110, 160)
    assert abs(red.window_s - 0.055846068) < 1e-8
    assert abs(red.busy_s - 0.026120697) < 1e-8
    assert abs(prog.expand_s - 0.010847513) < 1e-8
    assert abs(prog.batch_host_s - 0.023744102) < 1e-8
    assert abs(prog.masks_draw_s - 0.000739463) < 1e-8
    # the expansion runs in the dense lossy program only, inside its scan
    names = pt.op_names(str(PROGRAM))
    expand = {o.module for o in ops if "/sweep.expand/" in pt.op_name(o, names)}
    assert len(expand) == 1 and trace.SCAN_PROGRAM in expand.pop()
    assert 0 < prog.expand_s < red.scan_busy_s
    # all idle time is named, nearly all of it by the program's spans
    assert sum(prog.idle.values()) == pytest.approx(red.window_s - red.busy_s)
    own = sum(t for k, t in prog.idle.items() if k.startswith(pt.SPAN_PREFIX))
    assert own / sum(prog.idle.values()) > 0.97


def test_the_expansion_at_the_lossy_cell_shapes():
    """``record_trace.py <out> sensor_field.lossy`` on one TPU v5e: two
    sweeps of the lossy cell's grid (G = 120, N padded to 256, E = 2810,
    F = 256) at T = 40. There the TPU compiler turns the expansion's scatter
    into sorts and fusions without metadata, which take most of a round;
    named by their users, they fall under ``sweep.expand``."""
    red, prog = pt.load_and_reduce(str(CELL))
    ops, _ = trace.load(str(CELL))
    names = pt.op_names(str(CELL))
    expand = {o.name for o in ops if o.leaf and trace.SCAN_PROGRAM in o.module
              and "/sweep.expand/" in pt.op_name(o, names)}
    assert {"sort.4", "sort.5", "fusion.38", "fusion.39", "fusion.40",
            "fusion.41"} <= expand
    assert not any(o.kernel for o in ops if o.name in expand)
    # what this trace reduced to when it was recorded
    assert prog.sweeps == 2
    assert abs(red.scan_busy_s - 0.418764309) < 1e-8
    assert abs(prog.expand_s - 0.358853986) < 1e-8
    assert 0.8 < prog.expand_s / red.scan_busy_s < 0.9          # 4486 of 5235 us a round
    assert prog.span_counts["sweep.masks.draw"] == 2 * 60        # the 60 lossy cells


def test_program_run_reads_the_trace_the_harness_finds(tmp_path, monkeypatch, capsys):
    """``bench/program_run.py`` reduces the file ``bench.trace.find`` hands
    the harness, and prints the program's numbers after the result line."""
    import json
    import shutil

    from bench import program_run, run

    shutil.copy(PROGRAM, tmp_path / "t.xplane.pb")
    seen = []

    def fake_main(argv):
        seen.append(argv)
        trace.find(str(tmp_path))             # as bench.harness does
        print(json.dumps({"correct": True}))
        return 0

    monkeypatch.setattr(run, "main", fake_main)
    find = trace.find
    assert program_run.main(["--workload", "sensor_field.lossy", "--seed", "5",
                             "--seconds", "1"]) == 0
    assert trace.find is find
    assert seen == [["--workload", "sensor_field.lossy", "--seed", "5",
                     "--seconds", "1.0", "--trace", "1"]]
    result, line = capsys.readouterr().out.splitlines()
    prog = json.loads(line)["program"]
    red, want = pt.load_and_reduce(str(PROGRAM))
    assert prog["sweeps"] == 4
    assert prog["expand_us"] == pytest.approx(want.expand_s / (4 * 2000) * 1e6)
    assert prog["batch_host_s"] == pytest.approx(want.batch_host_s / 4)
    assert sum(prog["idle_s"].values()) == pytest.approx((red.window_s - red.busy_s) / 4)
    assert 97 < prog["idle_in_sweep_spans"] <= 100


def test_program_run_without_a_trace_exits_as_the_run(monkeypatch):
    from bench import program_run, run

    monkeypatch.setattr(run, "main", lambda argv: 2)
    assert program_run.main(["--workload", "sensor_field.static", "--seed", "1",
                             "--seconds", "1"]) == 2
