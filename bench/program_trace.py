"""The program's own spans and device scope in a profiler trace.

The sweep engine marks its host work with ``jax.profiler.TraceAnnotation``
spans named ``sweep.*`` (``sweep.run_batch`` holding ``sweep.prep``,
``sweep.put``, ``sweep.launch``, ``sweep.wait``, ``sweep.fetch``;
``sweep.masks`` holding one ``sweep.masks.draw`` per sampled cell) and its
in-scan dense mask expansion with ``jax.named_scope("sweep.expand")``, which
lands in each HLO instruction's ``op_name``. ``jax.profiler.ProfileData``
does not expose ``op_name``; the trace holds the HLO of every program it
ran (the ``/host:metadata`` plane), and ``op_names`` reads it from the
``.xplane.pb`` wire format directly. The TPU compiler makes some
instructions without metadata: at the lossy cell's size the expansion's
scatter becomes sorts and fusions that carry none, and take 99 % of its
device time. ``scopes`` gives such an instruction the ``op_name`` its
users agree on.

``reduce`` takes ``bench.trace.load``'s device operations and harness spans
and the program's spans, on the same clock, to:

* ``expand_s`` — the union of the sweep program's device operations under
  ``sweep.expand`` inside the window, over the chips used;
* ``batch_host_s`` — ``sweep.run_batch`` less its ``sweep.wait``: the host
  side of ``run_batch``;
* ``masks_draw_s`` — the ``sweep.masks.draw`` spans: per-cell RNG draws;
* ``idle`` — every idle stretch of the window on the first chip, split at
  span boundaries and summed by the innermost span open there (``none``
  outside every span).

All sums cover the window; ``sweeps`` (the harness's ``bench.sweep`` spans)
turns them into per-sweep numbers. A trace of a program without these
spans and scope gives zeros.
"""
from __future__ import annotations

import collections
import dataclasses
import re

from . import trace

SPAN_PREFIX = "sweep."
EXPAND_SCOPE = "sweep.expand"
PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one protobuf message: an int for varints,
    a memoryview for length-delimited fields, raw bytes for fixed ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _ints(values) -> list[int]:
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def scopes(instructions) -> dict[str, str]:
    """{name: op_name} of one HLO computation's ``(id, name, op_name,
    operand ids)`` in the order the computation lists them (operands before
    users). An instruction the compiler made without metadata takes the
    op_name its users agree on, where they name one and only one."""
    by_id = {i: (name, op) for i, name, op, _ in instructions}
    users: dict[int, list[int]] = collections.defaultdict(list)
    for i, _, _, operands in instructions:
        for o in operands:
            users[o].append(i)
    resolved: dict[int, str] = {}
    for i, _, op, _ in reversed(instructions):
        if not op:
            names = {resolved.get(u, "") for u in users[i]} - {""}
            op = names.pop() if len(names) == 1 else ""
        resolved[i] = op
    return {by_id[i][0]: op for i, op in resolved.items()}


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5
# (map entries: key = 1, value = 2); XEventMetadata.id = 1, .stats = 5;
# XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .bytes_value = 6.
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
# .metadata = 7, .id = 35, .operand_ids = 36; OpMetadata.op_name = 2.
def _hlo_modules(data):
    """(program id, HloModuleProto) of every program in the profiler's
    ``/host:metadata`` plane."""
    for field, plane in _fields(data):
        if field != 1:
            continue
        fields = list(_fields(plane))
        if bytes(dict(fields).get(2, b"")) != b"/host:metadata":
            continue
        stat_names = {}
        for f, v in fields:
            if f == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for f, v in fields:
            if f != 4:
                continue
            event = list(_fields(dict(_fields(v)).get(2, b"")))
            for stat in (dict(_fields(s)) for k, s in event if k == 5):
                if stat_names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                    yield dict(event).get(1), dict(_fields(stat[6])).get(1, b"")


def _instruction(inst) -> tuple:
    """(id, name, op_name, operand ids) of one HloInstructionProto."""
    parts = list(_fields(inst))
    d = dict(parts)
    meta = dict(_fields(d.get(7, b"")))
    return (d.get(35), bytes(d.get(1, b"")).decode(),
            bytes(meta.get(2, b"")).decode(errors="replace"),
            _ints(v for k, v in parts if k == 36))


def op_names(path: str) -> dict[tuple[int, str], str]:
    """{(program id, HLO instruction name): op_name} of every program whose
    HLO the trace holds, with ``scopes`` naming the instructions that carry
    no metadata."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = {}
    for program, module in _hlo_modules(data):
        for k, comp in _fields(module):
            if k == 3:
                named = scopes([_instruction(i) for k2, i in _fields(comp) if k2 == 2])
                out.update(((program, n), op) for n, op in named.items() if op)
    return out


def program_spans(path: str) -> list[trace.Span]:
    """The program's ``sweep.*`` host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [trace.Span(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(SPAN_PREFIX)]


def op_name(op: trace.Op, names: dict) -> str:
    m = PROGRAM_ID.search(op.module)
    return names.get((int(m.group(1)), op.name), "") if m else ""


@dataclasses.dataclass
class ProgramReduction:
    sweeps: int
    expand_s: float
    batch_host_s: float
    masks_draw_s: float
    idle: dict                  # innermost span -> idle seconds of the window
    span_counts: dict           # sweep.* span -> how many lie in the window


def _in_window(spans, window) -> list[trace.Span]:
    return [s for s in spans if trace.length(trace.clip([(s.start, s.end)], window)) > 0]


def idle_by_span(ops: list[trace.Op], spans: list[trace.Span]) -> dict[str, float]:
    """Idle seconds of the window on the first chip, split at every span
    boundary and summed by the innermost span (the shortest) open there."""
    window = trace.union((s.start, s.end) for s in spans if s.name == trace.WINDOW_SPAN)
    devices = sorted({o.device for o in ops})
    busy = trace.clip(trace.union((o.start, o.end) for o in ops
                                  if devices and o.device == devices[0]), window)
    gaps, b = [], 0
    for ws, we in window:
        cur = ws
        while b < len(busy) and busy[b][0] < we:
            if busy[b][0] > cur:
                gaps.append((cur, busy[b][0]))
            cur = max(cur, busy[b][1])
            b += 1
        if we > cur:
            gaps.append((cur, we))
    points = sorted({p for s in spans for p in (s.start, s.end)})
    segments = []
    for lo, hi in zip(points, points[1:]):
        mid = 0.5 * (lo + hi)
        open_ = [s for s in spans if s.start <= mid < s.end]
        label = min(open_, key=lambda s: s.end - s.start).name if open_ else "none"
        segments.append((lo, hi, label))
    out: dict[str, float] = {}

    def add(label, t):
        out[label] = out.get(label, 0.0) + t

    k = 0
    for s, e in gaps:
        while k < len(segments) and segments[k][1] <= s:
            k += 1
        cur, j = s, k
        while j < len(segments) and segments[j][0] < e:
            lo, hi, label = segments[j]
            if lo > cur:                  # before the first span boundary
                add("none", lo - cur)
                cur = lo
            add(label, min(hi, e) - cur)
            cur = min(hi, e)
            j += 1
        if e > cur:
            add("none", e - cur)
    return out


def reduce(ops: list[trace.Op], spans: list[trace.Span], names: dict) -> ProgramReduction:
    """``ops`` and ``spans`` as ``bench.trace.load`` gives them, with the
    program's spans added to ``spans``; ``names`` from ``op_names``."""
    window = trace.union((s.start, s.end) for s in spans if s.name == trace.WINDOW_SPAN)
    sweeps = sum(1 for s in spans if s.name == trace.WINDOW_SPAN)
    devices = sorted({o.device for o in ops})
    expand = [o for o in ops if o.leaf and trace.SCAN_PROGRAM in o.module
              and f"/{EXPAND_SCOPE}/" in op_name(o, names)]
    expand_s = sum(trace.length(trace.clip(
        trace.union((o.start, o.end) for o in expand if o.device == d), window))
        for d in devices) / max(len(devices), 1)
    own = _in_window([s for s in spans if s.name.startswith(SPAN_PREFIX)], window)

    def total(name):
        return sum(s.end - s.start for s in own if s.name == name)

    return ProgramReduction(
        sweeps=sweeps, expand_s=expand_s,
        batch_host_s=total("sweep.run_batch") - total("sweep.wait"),
        masks_draw_s=total("sweep.masks.draw"), idle=idle_by_span(ops, spans),
        span_counts=dict(collections.Counter(s.name for s in own)))


def load_and_reduce(path: str) -> tuple[trace.Reduction, ProgramReduction]:
    """Both reductions of one ``.xplane.pb``: ``bench.trace``'s, unchanged,
    and the program's, with idle time named by the program's spans."""
    ops, spans = trace.load(path)
    ours = program_spans(path)
    return trace.reduce(ops, spans), reduce(ops, spans + ours, op_names(path))
