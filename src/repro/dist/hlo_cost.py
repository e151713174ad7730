"""Trip-count-weighted HLO cost model (dry-run §Roofline).

``compiled.cost_analysis()`` counts every computation once, but the real
schedule executes while-loop bodies ``known_trip_count`` times — a
grad-accum scan with 32 microbatches is 32x the FLOPs XLA reports, and a
ring exchange inside a loop is g-1 permutes, not one.  ``weighted_cost``
walks the module's call graph (while bodies/conditions, fusions, calls,
reducers, branches), multiplies every computation's cost by the product
of trip counts on its call chain from ENTRY, and returns:

* ``flops``  — dot/convolution FLOPs, trip-weighted,
* ``bytes``  — operand+result buffer traffic per instruction (the same
  convention as XLA's "bytes accessed"), trip-weighted,
* ``collectives`` — :class:`repro.dist.hlo.Collective` records with their
  ``trips`` field set, ready for ``summarize``/``axis_bytes``.

Costs are per-device: shapes in partitioned HLO are already the local
shards.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from .hlo import (Collective, collective_stats, parse_computations,
                  shape_bytes, split_ops)

_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n["\s:]+(\d+)')
_CALL_ATTR_RE = re.compile(
    r"(?:to_apply|calls|body|condition|branch_computations)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
_DIMS_RE = re.compile(r"\{([0-9,]*)\}")

_SKIP_BYTES = ("parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all")


@dataclass
class WeightedCost:
    flops: float = 0.0
    bytes: float = 0.0
    collectives: list[Collective] = field(default_factory=list)


def _first_shape_dims(type_str: str) -> list[int]:
    m = re.search(r"\w+\[([0-9,]*)\]", type_str)
    if not m or not m.group(1):
        return []
    return [int(x) for x in m.group(1).split(",")]


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= x
    return out


def _operand_types(operands: str) -> list[str]:
    """Split an operand list on top-level commas -> per-operand type text."""
    parts, depth, cur = [], 0, []
    for ch in operands:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def _op_flops(kind: str, result_type: str, operands: str, attrs: str) -> float:
    if kind == "dot":
        out = _prod(_first_shape_dims(result_type))
        ops = _operand_types(operands)
        lhs = _first_shape_dims(ops[0]) if ops else []
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
        contracted = 1
        if m and m.group(1) and lhs:
            for d in m.group(1).split(","):
                i = int(d)
                if i < len(lhs):
                    contracted *= lhs[i]
        return 2.0 * out * contracted
    if kind == "convolution":
        out = _prod(_first_shape_dims(result_type))
        ops = _operand_types(operands)
        rhs = _first_shape_dims(ops[1]) if len(ops) > 1 else []
        m = re.search(r"dim_labels=\w+_(\w+)->", attrs)
        if m and rhs and len(m.group(1)) == len(rhs):
            # kernel contributes every rhs dim except the output-feature 'o'
            contracted = _prod(d for d, lab in zip(rhs, m.group(1))
                               if lab != "o")
        else:
            contracted = _prod(rhs[:-1]) if rhs else 1
        return 2.0 * out * contracted
    return 0.0


def _comp_costs(lines: list[str]) -> tuple[float, float]:
    flops = byts = 0.0
    for result_type, kind, operands, attrs in split_ops(lines):
        flops += _op_flops(kind, result_type, operands, attrs)
        if kind not in _SKIP_BYTES:
            byts += shape_bytes(result_type) + shape_bytes(operands)
    return flops, byts


def _call_edges(lines: list[str], known: set) -> list[tuple[str, int]]:
    """(callee, trip_weight) edges out of a computation's instructions."""
    edges: list[tuple[str, int]] = []
    for line in lines:
        trip = 1
        m = _TRIP_RE.search(line)
        if m:
            trip = int(m.group(1))
        for ref in _CALL_ATTR_RE.findall(line):
            for name in re.findall(r"%?([\w.\-]+)", ref):
                if name in known:
                    edges.append((name, trip))
    return edges


def multiplicities(comps: dict[str, list[str]], entry: str) -> dict[str, int]:
    """Execution count of every computation, trip-count weighted, assuming
    each call site runs once per execution of its caller (call graphs from
    XLA are DAGs; cycles would indicate a parse bug and are cut off)."""
    known = set(comps)
    mult = {name: 0 for name in comps}
    if entry not in comps:
        return mult
    mult[entry] = 1
    # A computation may be reached before all its callers are settled, so
    # recompute from the callers to fixpoint (bounded by the DAG depth).
    for _ in range(len(comps) + 1):
        changed = False
        new_mult = {name: 0 for name in comps}
        new_mult[entry] = 1
        for name in comps:
            if mult.get(name, 0) <= 0:
                continue
            for callee, trip in _call_edges(comps[name], known):
                if callee == name:
                    continue
                new_mult[callee] = new_mult.get(callee, 0) \
                    + mult[name] * trip
        for name in comps:
            m = max(new_mult.get(name, 0), 1 if name == entry else 0)
            if m != mult.get(name):
                mult[name] = m
                changed = True
        if not changed:
            break
    return mult


def weighted_cost(txt: str, *, model: int = 1, data: int = 1,
                  node: int = 1) -> WeightedCost:
    """Parse compiled-HLO text into a trip-weighted per-device cost."""
    comps, entry = parse_computations(txt)
    mult = multiplicities(comps, entry)
    wc = WeightedCost()
    for name, lines in comps.items():
        m = mult.get(name, 0)
        if m <= 0:
            continue
        f, b = _comp_costs(lines)
        wc.flops += m * f
        wc.bytes += m * b
    for c in collective_stats(txt, model=model, data=data, node=node):
        c.trips = max(mult.get(c.computation, 1), 1)
        wc.collectives.append(c)
    return wc
