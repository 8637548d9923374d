"""From a JAX profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
operations (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, named by
their HLO text), the program each ran in (the ``XLA Modules`` line), whether
each is a Mosaic kernel (``custom_call_target="tpu_custom_call"``), and the
harness's own host spans (``jax.profiler.TraceAnnotation``, named
``bench.*``). Device and host events share the profiler's clock. ``reduce`` turns them into:

* ``window_s`` — the length of the harness's sweep spans (the timed window);
* ``busy_s`` — the union of device operation intervals inside them, over the
  chips used;
* ``scan_busy_s`` — the same for the sweep program's operations alone;
* ``kernel_s`` — the time of Mosaic kernel events (``tpu_custom_call``)
  inside the sweep program, whatever the kernels are named;
* ``device_ops`` — the ten device operations that took most time;
* ``idle_gaps`` — the ten longest stretches of the window with nothing on
  the device, each named by the innermost harness span open at the time.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.sweep"
SPAN_PREFIX = "bench."
SCAN_PROGRAM = "_sweep_scan"
KERNEL_MARK = "tpu_custom_call"
MIN_GAP = 1e-6             # shorter idle stretches are not listed


@dataclasses.dataclass(frozen=True)
class Op:
    start: float               # seconds on the profiler clock
    end: float
    name: str
    module: str
    kernel: bool
    device: str
    leaf: bool = True          # no other operation runs inside it


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    scan_busy_s: float
    kernel_s: float
    devices: int
    device_ops: list
    idle_gaps: list
    spans: dict


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(text: str) -> str:
    """``fusion.38`` from the event's HLO text ``%fusion.38 = f32[...] ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _nest(events) -> list[tuple]:
    """(start, end, text, leaf) of one line's events: a loop's event spans
    the events of its body, so only the innermost are leaves."""
    evs = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name) for e in events)
    return [(s, e, t, not (i + 1 < len(evs) and evs[i + 1][0] < e))
            for i, (s, e, t) in enumerate(evs)]


def load(path: str) -> tuple[list[Op], list[Span]]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            modules = sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                             for e in (lines["XLA Modules"].events
                                       if "XLA Modules" in lines else ()))
            m = 0
            for s, e, text, leaf in _nest(lines["XLA Ops"].events
                                          if "XLA Ops" in lines else ()):
                while m < len(modules) and modules[m][1] <= s:
                    m += 1
                module = modules[m][2] if m < len(modules) and modules[m][0] <= s else ""
                ops.append(Op(s, e, short_name(text), module,
                              leaf and KERNEL_MARK in text, plane.name, leaf))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                          ev.name))
    return ops, spans


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window) -> list[tuple[float, float]]:
    """Parts of ``intervals`` (merged) inside the merged ``window``."""
    out, w = [], 0
    for s, e in intervals:
        while w < len(window) and window[w][1] <= s:
            w += 1
        k = w
        while k < len(window) and window[k][0] < e:
            lo, hi = max(s, window[k][0]), min(e, window[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def reduce(ops: list[Op], spans: list[Span]) -> Reduction:
    window = union((s.start, s.end) for s in spans if s.name == WINDOW_SPAN)
    devices = sorted({o.device for o in ops})
    busy = sum(length(clip(union((o.start, o.end) for o in ops if o.device == d),
                           window)) for d in devices)
    scan = [o for o in ops if SCAN_PROGRAM in o.module]
    scan_busy = sum(length(clip(union((o.start, o.end) for o in scan if o.device == d),
                                window)) for d in devices)
    kernel = sum(length(clip([(o.start, o.end)], window)) for o in scan if o.kernel)

    per_op: dict[str, float] = {}
    for o in ops:
        if not o.leaf:
            continue
        t = length(clip([(o.start, o.end)], window))
        if t > 0:
            per_op[o.name] = per_op.get(o.name, 0.0) + t
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]

    # idle stretches of the window, on the first chip, named by the
    # innermost harness span open at their midpoint
    first = union((o.start, o.end) for o in ops if devices and o.device == devices[0])
    gaps = []
    for ws, we in window:
        cur = ws
        for s, e in clip(first, [(ws, we)]):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if we > cur:
            gaps.append((cur, we))
    named = []
    gaps = [g for g in gaps if g[1] - g[0] >= MIN_GAP]
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp.start <= mid < sp.end]
        label = min(open_, key=lambda sp: sp.end - sp.start).name if open_ else "none"
        named.append([label, e - s])

    by_name: dict[str, list[float]] = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp.end - sp.start)
    n = max(len(devices), 1)
    return Reduction(window_s=length(window), busy_s=busy / n, scan_busy_s=scan_busy / n,
                     kernel_s=kernel / n, devices=len(devices),
                     device_ops=[[k, v / n] for k, v in top], idle_gaps=named,
                     spans=by_name)
