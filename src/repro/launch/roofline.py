"""Roofline-term extraction from compiled (SPMD-partitioned) HLO.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
empirically: a 4-iteration scan reports ~1 layer of flops), which would make
every scan-over-layers model look 'free'. This module therefore walks the
HLO text itself:

  * computations are parsed into op lists with output/operand shapes;
  * the call graph is walked from ENTRY with multipliers — ``while`` bodies
    multiply by their ``backend_config known_trip_count`` (XLA records it for
    counted loops, i.e. every lax.scan), fusions/calls/conditionals recurse
    at x1;
  * FLOPs: 2 * prod(out) * prod(contracted dims) per ``dot`` (matmul-dominated
    models; elementwise/transcendental excluded, <1% for these workloads);
  * HBM bytes: sum of operand+output bytes over *fusion-boundary* ops (the
    post-fusion instruction stream is exactly what goes through HBM on TPU);
  * collective wire bytes per device, with ring-algorithm factors:
    all-reduce 2S(n-1)/n, all-gather S_out(n-1)/n, reduce-scatter S_in(n-1)/n,
    all-to-all S(n-1)/n, collective-permute S.

Hardware constants are keyed by ``device_kind`` (``PEAKS``; ``peaks(kind)``
raises for a kind not listed, so no chip is ever read against another's
rates). Collectives whose group size equals the pod count in a multi-pod
lowering are tagged DCN.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["PEAKS", "HloCost", "analyze_hlo", "peaks", "roofline_report"]

# Per-chip peaks by ``jax.Device.device_kind``. TPU v5e (reported as
# "TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s chip-to-chip ICI (~50 GB/s per link over four
# links); ~6.25 GB/s/chip DCN is the cross-pod planning figure.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,   # bf16 per chip
        "hbm_bw": 819e9,        # bytes/s per chip
        "ici_bw": 50e9,         # bytes/s per link
        "dcn_bw": 6.25e9,       # bytes/s per chip, cross-pod
    },
}


def peaks(device_kind: str) -> dict:
    """The peak-rate table of one chip kind; an unlisted kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s4": 0.5, "u4": 0.5,
}

_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z]*\d*(?:fn|fnuz)?)\[([\d,]*)\]")
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)
# ops that mark fusion boundaries => HBM traffic on their operands/outputs
_TRAFFIC_OPS = {
    "dot", "fusion", "copy", "convolution", "reduce", "transpose", "gather",
    "scatter", "dynamic-slice", "dynamic-update-slice", "broadcast", "reshape",
    "concatenate", "slice", "pad", "reverse", "sort", "rng", "iota", "select",
    "compare", "add", "multiply", "subtract", "divide", "exponential", "tanh",
    "convert", "reduce-window", "cholesky", "triangular-solve",
} | set(_COLLECTIVES)
_FREE_OPS = {"get-tuple-element", "tuple", "parameter", "bitcast", "constant",
             "after-all", "custom-call", "partition-id", "replica-id"}


def _shape_bytes(type_str: str) -> float:
    """Total bytes of one 'dtype[dims]' or a tuple '(t1, t2, ...)' string."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class _Op:
    name: str
    opcode: str
    out_type: str
    operands: list[str]
    attrs: str


_COMP_HDR = re.compile(r"^(ENTRY )?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_OP_LINE = re.compile(r"^\s+(?:ROOT )?%([\w\.\-]+) = (.+)$")
_OPCODE_RE = re.compile(r"^((?:\([^)]*\))|(?:[a-z0-9_\[\]\{\},\. ]+?))\s+([\w\-]+)\(")


def _parse_computations(text: str) -> tuple[dict, str | None]:
    comps: dict[str, list[_Op]] = {}
    entry = None
    cur: list[_Op] | None = None
    for line in text.splitlines():
        hdr = _COMP_HDR.match(line)
        if hdr:
            name = hdr.group(2)
            comps[name] = []
            cur = comps[name]
            if hdr.group(1):
                entry = name
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _OP_LINE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        om = _OPCODE_RE.match(rhs)
        if not om:
            # ops with no operands, e.g. 'f32[] constant(1)' handled above; skip others
            continue
        out_type, opcode = om.group(1).strip(), om.group(2)
        # operand list: first balanced (...) after opcode
        start = rhs.index(opcode + "(") + len(opcode) + 1
        depth, i = 1, start
        while i < len(rhs) and depth:
            if rhs[i] == "(":
                depth += 1
            elif rhs[i] == ")":
                depth -= 1
            i += 1
        inside = rhs[start : i - 1]
        attrs = rhs[i:]
        operands = re.findall(r"%([\w\.\-]+)", inside)
        cur.append(_Op(name, opcode, out_type, operands, attrs))
    return comps, entry


def _group_size(attrs: str) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:
        return int(m.group(2))
    m = re.search(r"source_target_pairs=", attrs)
    if m:
        return 2
    return 2


def _trip_count(attrs: str) -> int:
    m = re.search(r"known_trip_count[^0-9]{0,16}(\d+)", attrs)
    if m:
        return int(m.group(1))
    return 1


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0                 # per device
    hbm_bytes: float = 0.0             # per device (fusion-boundary estimate)
    wire_bytes_ici: float = 0.0        # per device
    wire_bytes_dcn: float = 0.0        # per device (pod-axis collectives)
    collectives: dict = dataclasses.field(default_factory=dict)  # kind -> bytes
    collective_counts: dict = dataclasses.field(default_factory=dict)
    dots: int = 0

    def terms(self, hw: dict) -> dict:
        t_c = self.flops / hw["peak_flops"]
        t_m = self.hbm_bytes / hw["hbm_bw"]
        t_net = self.wire_bytes_ici / hw["ici_bw"] + self.wire_bytes_dcn / hw["dcn_bw"]
        dom = max((t_c, "compute"), (t_m, "memory"), (t_net, "collective"))[1]
        return {
            "compute_s": t_c, "memory_s": t_m, "collective_s": t_net,
            "bound": dom,
            "step_s": max(t_c, t_m, t_net),
        }


def _wire_bytes(kind: str, in_b: float, out_b: float, n: int) -> float:
    if n <= 1:
        return 0.0
    f = (n - 1) / n
    if kind == "all-reduce":
        return 2 * out_b * f
    if kind == "all-gather":
        return out_b * f
    if kind == "reduce-scatter":
        return in_b * f
    if kind in ("all-to-all", "ragged-all-to-all"):
        return in_b * f
    return out_b  # collective-permute / broadcast


def analyze_hlo(text: str, num_pods: int = 1) -> HloCost:
    comps, entry = _parse_computations(text)
    if entry is None:
        raise ValueError("no ENTRY computation found")
    cost = HloCost()
    colls = defaultdict(float)
    counts = defaultdict(int)

    def shape_of(comp_ops: dict[str, _Op], name: str) -> str:
        op = comp_ops.get(name)
        return op.out_type if op else ""

    def walk(comp_name: str, mult: float, seen: tuple = (), kernel: bool = False):
        """kernel=True: inside a Pallas interpret body — its fusions/copies
        are VMEM traffic on real TPU, so only dot FLOPs are counted there;
        the kernel's HBM traffic is charged once at the grid-loop call site
        (operand/result block transfers)."""
        ops = comps.get(comp_name)
        if ops is None or comp_name in seen:
            return
        sym = {o.name: o for o in ops}
        for o in ops:
            out_b = _shape_bytes(o.out_type)
            in_b = sum(_shape_bytes(shape_of(sym, x)) for x in o.operands)
            if o.opcode == "dot":
                out_dims = _shape_dims(o.out_type)
                lhs = sym.get(o.operands[0])
                m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", o.attrs)
                k = 1
                if lhs is not None and m and m.group(1):
                    ldims = _shape_dims(lhs.out_type)
                    for d in m.group(1).split(","):
                        k *= ldims[int(d)]
                f = 2.0
                for d in out_dims:
                    f *= d
                cost.flops += f * k * mult  # 2*prod(out)*K
                cost.dots += int(mult)
            if o.opcode in _COLLECTIVES and not kernel:
                n = _group_size(o.attrs)
                wb = _wire_bytes(o.opcode, in_b, out_b, n) * mult
                colls[o.opcode] += wb
                counts[o.opcode] += int(mult)
                if num_pods > 1 and n == num_pods:
                    cost.wire_bytes_dcn += wb
                else:
                    cost.wire_bytes_ici += wb
            # HBM traffic: op-specific — indexed ops touch only the slice,
            # not the full operand (dynamic-slice inside a grid/scan loop
            # would otherwise count the whole buffer per iteration).
            if kernel:
                pass  # VMEM-level ops inside a Pallas body: no HBM charge
            elif o.opcode == "dynamic-slice":
                cost.hbm_bytes += 2 * out_b * mult
            elif o.opcode == "dynamic-update-slice":
                upd = _shape_bytes(shape_of(sym, o.operands[1])) if len(o.operands) > 1 else out_b
                cost.hbm_bytes += 2 * upd * mult
            elif o.opcode == "gather":
                cost.hbm_bytes += 2 * out_b * mult
            elif o.opcode == "scatter":
                upd = _shape_bytes(shape_of(sym, o.operands[2])) if len(o.operands) > 2 else out_b
                cost.hbm_bytes += 2 * upd * mult
            elif o.opcode in ("broadcast", "iota"):
                cost.hbm_bytes += out_b * mult
            elif o.opcode not in _FREE_OPS:
                cost.hbm_bytes += (out_b + in_b) * mult
            # recursion
            if o.opcode == "while":
                tc = _trip_count(o.attrs)
                m = re.search(r"body=%([\w\.\-]+)", o.attrs)
                body = m.group(1) if m else ""
                into_kernel = "_custom_call_lowering_rul" in body
                if into_kernel and not kernel:
                    # Pallas grid loop: charge block I/O once (operand +
                    # result arrays stream HBM<->VMEM exactly once per call)
                    cost.hbm_bytes += (in_b + out_b) * mult
                for key in ("body", "condition"):
                    m = re.search(key + r"=%([\w\.\-]+)", o.attrs)
                    if m:
                        walk(m.group(1), mult * tc, seen + (comp_name,),
                             kernel=kernel or into_kernel)
            elif o.opcode in ("call", "conditional", "async-start"):
                for m in re.finditer(r"(?:to_apply|branch_computations=\{|called_computations=\{)[%]?([\w\.\-]+)", o.attrs):
                    into_kernel = "_custom_call_lowering_rul" in m.group(1)
                    if into_kernel and not kernel:
                        cost.hbm_bytes += (in_b + out_b) * mult
                    walk(m.group(1), mult, seen + (comp_name,),
                         kernel=kernel or into_kernel)
            elif o.opcode == "fusion":
                m = re.search(r"calls=%([\w\.\-]+)", o.attrs)
                if m:
                    _walk_fusion_flops(m.group(1), mult, seen + (comp_name,))

    def _walk_fusion_flops(comp_name: str, mult: float, seen: tuple):
        """Inside fusions only dots matter (internal traffic is VMEM)."""
        ops = comps.get(comp_name)
        if ops is None or comp_name in seen:
            return
        sym = {o.name: o for o in ops}
        for o in ops:
            if o.opcode == "dot":
                out_dims = _shape_dims(o.out_type)
                lhs = sym.get(o.operands[0])
                m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", o.attrs)
                k = 1
                if lhs is not None and m and m.group(1):
                    ldims = _shape_dims(lhs.out_type)
                    for d in m.group(1).split(","):
                        k *= ldims[int(d)]
                f = 2.0
                for d in out_dims:
                    f *= d
                cost.flops += f * k * mult
                cost.dots += int(mult)
            elif o.opcode == "fusion":
                m = re.search(r"calls=%([\w\.\-]+)", o.attrs)
                if m:
                    _walk_fusion_flops(m.group(1), mult, seen + (comp_name,))

    walk(entry, 1.0)
    cost.collectives = dict(colls)
    cost.collective_counts = dict(counts)
    return cost


def roofline_report(cost: HloCost, chips: int, model_flops_global: float | None,
                    hw: dict) -> dict:
    terms = cost.terms(hw)
    rep = {
        "chips": chips,
        "flops_per_device": cost.flops,
        "hbm_bytes_per_device": cost.hbm_bytes,
        "wire_ici_per_device": cost.wire_bytes_ici,
        "wire_dcn_per_device": cost.wire_bytes_dcn,
        **terms,
        "collectives": cost.collectives,
        "collective_counts": cost.collective_counts,
    }
    if model_flops_global:
        hlo_global = cost.flops * chips
        rep["model_flops_global"] = model_flops_global
        rep["useful_flop_ratio"] = model_flops_global / max(hlo_global, 1.0)
        # roofline fraction: useful model flops per device-second at the
        # achieved (bound-limited) step time
        rep["roofline_fraction"] = (
            model_flops_global / chips / hw["peak_flops"] / max(terms["step_s"], 1e-30)
        )
    return rep
