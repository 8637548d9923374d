"""Pass 3: mesh/kernel contracts.

Traces the engine's pallas program — the program every device runs on its
own block of cells under a mesh (the engine wraps the whole scan in
``shard_map`` over G, so no kernel is ever left to the partitioner) — and
checks, per ``pallas_call`` equation:

- ``kernel-tile-divisibility`` (error): a BlockSpec tile does not divide
  its operand extent — the kernel would read OOB-masked garbage or the
  lowering would fail at compile time, long after the sweep was scheduled.
- ``kernel-vmem-budget`` (error): the per-grid-step block working set
  (every input/output block, double-buffered) exceeds ``VMEM_BLOCK_BUDGET``
  — half the TPU v5e's 16 MiB default scoped VMEM, leaving the rest to
  Mosaic's own temporaries.

Both the dense batched-round program and the sparse ELL segment program
are traced; ``dist/gossip.py``'s coverage of registry algorithms gets an
advisory ``mesh-dist-coverage`` (info) for specs with no dist variant.
"""

from __future__ import annotations

from .findings import AnalysisFinding, source_of
from . import trace_utils as tu

PASS = "mesh-kernel"

VMEM_BLOCK_BUDGET = 8 * 1024 * 1024  # bytes, double-buffered block set


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    name = getattr(info, "name", None) or eqn.params.get("name")
    return str(name) if name else "pallas_call"


def _kernel_finding(rule, severity, message, obj):
    from repro.kernels import ops

    file, line = source_of(ops.use_interpret)  # anchor at kernels/ops.py
    return AnalysisFinding(
        rule=rule, severity=severity, message=message, obj=obj,
        file=file, line=line, passname=PASS)


def _block_shapes(eqn):
    """(block_shape, operand_shape, dtype) triples for inputs AND outputs."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return []
    mappings = list(gm.block_mappings)
    in_avals = [v.aval for v in eqn.invars]
    out_avals = list(eqn.params.get("out_avals") or
                     [v.aval for v in eqn.outvars])
    # index-style mappings align leading with inputs, trailing with outputs
    n_in = len(mappings) - len(out_avals)
    avals = in_avals[-n_in:] if 0 <= n_in <= len(in_avals) else in_avals
    avals = list(avals) + out_avals
    out = []
    for bm, aval in zip(mappings, avals):
        bs = tuple(int(d) for d in bm.block_shape
                   if isinstance(d, int) or hasattr(d, "__index__"))
        out.append((bs, tuple(aval.shape), aval.dtype))
    return out


def check_pallas_eqn(eqn) -> list[AnalysisFinding]:
    name = _kernel_name(eqn)
    findings = []
    vmem = 0
    for bs, shape, dtype in _block_shapes(eqn):
        if len(bs) == len(shape):
            for bd, sd in zip(bs, shape):
                if bd and sd % bd != 0:
                    findings.append(_kernel_finding(
                        "kernel-tile-divisibility", "error",
                        f"BlockSpec tile {bs} does not divide operand "
                        f"extent {shape} (dim {sd} % {bd} != 0)", name))
                    break
        n_elem = 1
        for bd in (bs if bs else shape):
            n_elem *= max(int(bd), 1)
        vmem += n_elem * dtype.itemsize
    if 2 * vmem > VMEM_BLOCK_BUDGET:  # double-buffered pipeline working set
        findings.append(_kernel_finding(
            "kernel-vmem-budget", "error",
            f"per-step block working set 2*{vmem}B exceeds the kernel "
            f"VMEM block budget {VMEM_BLOCK_BUDGET}B", name))
    return findings


def _check_dist_coverage() -> list[AnalysisFinding]:
    from repro.core.algorithms import dist_variant, registered_algorithms
    from repro.dist import gossip

    file, line = source_of(gossip._register_dist_variants)
    exempt = getattr(gossip, "DIST_EXEMPT", ())
    findings = []
    for name in registered_algorithms():
        if dist_variant(name) is None and name not in exempt:
            findings.append(AnalysisFinding(
                rule="mesh-dist-coverage", severity="info",
                message="no dist/gossip variant registered (multi-process "
                "runs fall back to the single-host engine) and not listed "
                "in dist.gossip.DIST_EXEMPT",
                obj=name, file=file, line=line, passname=PASS))
    return findings


def check_mesh_kernels(algorithms=None) -> list[AnalysisFinding]:
    from repro.core.algorithms import registered_algorithms

    specs = tuple(algorithms or registered_algorithms())
    findings: list[AnalysisFinding] = []
    traces = []
    try:
        traces.append(tu.trace_engine(specs, "pallas"))
    except Exception as exc:
        findings.append(_kernel_finding(
            "engine-trace-failed", "error",
            f"dense pallas grid failed to trace: {exc}",
            "sweep.engine[pallas]"))
    try:
        traces.append(tu.trace_engine_sparse(specs))
    except Exception as exc:
        findings.append(_kernel_finding(
            "engine-trace-failed", "error",
            f"sparse pallas grid failed to trace: {exc}",
            "sweep.engine[pallas-sparse]"))
    for closed in traces:
        for eqn in tu.iter_eqns(closed.jaxpr):
            if eqn.primitive.name == "pallas_call":
                findings.extend(check_pallas_eqn(eqn))
    if algorithms is None:  # registry-wide advisory, not per-spec
        findings.extend(_check_dist_coverage())
    # the same kernel appears once per partition/branch: dedup exact repeats
    seen, uniq = set(), []
    for f in findings:
        key = (f.rule, f.obj, f.message)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq
