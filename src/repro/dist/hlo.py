"""Communication introspection from compiled HLO text (DESIGN.md §7).

``plan_bytes`` (core.shrinkage) gives the *analytic* inter-node payload —
what the algorithm intends to move.  This module measures what the XLA
schedule *actually* moves: parse ``compiled.as_text()`` into one record
per collective (kind, payload bytes, replica groups, mesh axis, fabric
tier) so dry-runs and the training loop can report both numbers side by
side and catch regressions where GSPMD silently materializes extra
all-gathers (e.g. a replicated index tensor — see engine.py's sharding
notes for two real incidents).

Device-id geometry: meshes here are row-major ``(pod, data, model)`` with
``model`` minor-most, so a replica group's member stride identifies the
axis it spans — stride 1 is tensor-parallel traffic on the fastest links,
stride ``model`` walks the data axis (intra-node if the group stays
within one ``node_size`` block of workers, inter-node otherwise), and
stride ``model*data`` crosses the pod boundary (slow DCI fabric).

Both replica-group encodings XLA emits are handled: literal
``{{0,2},{1,3}}`` and iota ``[2,4]<=[4,2]T(1,0)``.
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field
from typing import Optional

import numpy as np

# collective op -> per-device wire-byte multiplier given group size g and
# (operand_bytes, result_bytes); ring algorithms assumed (standard model)
_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
          "collective-permute", "collective-broadcast", "ragged-all-to-all")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]\w*)\[([0-9,]*)\]")


@dataclass
class Collective:
    """One collective instruction in the compiled module."""

    kind: str                 # all-reduce | all-gather | ...
    payload_bytes: int        # per-device operand bytes on the wire
    result_bytes: int
    wire_bytes: float         # est. per-device fabric traffic (ring model)
    group_size: int
    n_groups: int
    axis: str                 # model | data | pod | mixed | self
    fabric: str               # tp | intra_node | inter_node | inter_pod | local
    channel_id: Optional[int]
    computation: str
    trips: int = 1            # trip-count weight (see hlo_cost)
    # bytes of each operand tensor: XLA's combiners merge independent
    # collectives into one instruction carrying several tensors
    tensor_bytes: tuple = ()
    replica_groups: list = field(default_factory=list, repr=False)

    @property
    def weighted_wire_bytes(self) -> float:
        return self.wire_bytes * self.trips


# ---------------------------------------------------------------------------
# low-level text parsing (shared with hlo_cost)
# ---------------------------------------------------------------------------


def shape_bytes(type_str: str) -> int:
    """Total bytes of every ``dtype[dims]`` shape inside ``type_str``."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def shape_elems(type_str: str) -> int:
    """Element count of the first shape inside ``type_str``."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _match_paren(s: str, start: int) -> int:
    """Index just past the ')' matching the '(' at ``start``."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*")


def split_op(line: str) -> Optional[tuple[str, str, str, str]]:
    """Split an HLO instruction line into (result_type, kind, operands,
    attrs); None for non-instruction lines."""
    m = _ASSIGN_RE.match(line)
    if not m:
        return None
    rest = line[m.end():].strip()
    if rest.startswith("("):          # tuple-typed result
        end = _match_paren(rest, 0)
        result_type, rest = rest[:end], rest[end:].strip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        result_type, rest = rest[:sp], rest[sp + 1:].strip()
    p = rest.find("(")
    if p < 0:
        return None
    kind = rest[:p].strip()
    end = _match_paren(rest, p)
    operands = rest[p + 1:end - 1]
    attrs = rest[end:]
    return result_type, kind, operands, attrs


_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_REF_RE = re.compile(r"%([\w.\-]+)")


def split_ops(lines: list[str]):
    """:func:`split_op` over one computation's instruction lines.  Newer
    XLA prints operands as bare ``%name`` references; those get the type
    of their defining instruction, so operand byte/shape parsing sees the
    same ``dtype[dims] %name`` text either way."""
    parsed = [(line, split_op(line)) for line in lines]
    types = {_NAME_RE.match(line).group(1): p[0]
             for line, p in parsed if p is not None}
    for _, p in parsed:
        if p is None:
            continue
        result_type, kind, operands, attrs = p
        if not _SHAPE_RE.search(operands):
            operands = _REF_RE.sub(
                lambda m: f"{types.get(m.group(1), '')} %{m.group(1)}",
                operands)
        yield result_type, kind, operands, attrs


_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{$")


def parse_computations(txt: str) -> tuple[dict[str, list[str]], str]:
    """Split module text into {computation_name: [instruction lines]} plus
    the ENTRY computation's name."""
    comps: dict[str, list[str]] = {}
    entry = ""
    current: Optional[str] = None
    for line in txt.splitlines():
        m = _COMP_RE.match(line.rstrip())
        if m:
            current = m.group(2)
            comps[current] = []
            if m.group(1):
                entry = current
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is not None and "=" in line:
            comps[current].append(line)
    return comps, entry


def _parse_replica_groups(attrs: str) -> list[list[int]]:
    m = re.search(r"replica_groups=\{\{([^=]*?)\}\}", attrs)
    if m:
        return [[int(x) for x in grp.split(",") if x.strip()]
                for grp in m.group(1).split("},{")]
    m = re.search(r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\]"
                  r"(?:T\(([0-9,]+)\))?", attrs)
    if m:     # iota form: reshape(transpose(iota))
        dims = [int(x) for x in m.group(1).split(",")]
        src = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(src))).reshape(src)
        if m.group(3):
            ids = ids.transpose([int(x) for x in m.group(3).split(",")])
        return np.ascontiguousarray(ids).reshape(dims).tolist()
    return []


def _classify(groups: list[list[int]], model: int, data: int, node: int
              ) -> tuple[str, str]:
    """Map replica groups onto (mesh axis, fabric tier) via member stride."""
    if not groups or max(len(g) for g in groups) <= 1:
        return "self", "local"
    g = sorted(groups[0])
    strides = {b - a for a, b in zip(g, g[1:])}
    if len(strides) != 1:
        return "mixed", "inter_node"
    s = strides.pop()
    if s < model:
        return "model", "tp"
    if s % model == 0 and s < model * data:
        step = s // model                # stride in data-axis ranks
        span = step * (len(g) - 1) + 1   # data ranks covered by the group
        if step == 1 and span <= node:
            return "data", "intra_node"
        return "data", "inter_node"
    return "pod", "inter_pod"


def _wire_bytes(kind: str, g: int, operand_b: int, result_b: int) -> float:
    # ring model shared with the analytic accounting (repro.comm)
    from ..comm import collective_wire_bytes
    return collective_wire_bytes(kind, g, operand_b)


def _permute_groups(attrs: str) -> list[list[int]]:
    m = re.search(r"source_target_pairs=(\{\{.*?\}\})", attrs)
    if not m:
        return []
    pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1))
    return [[int(a), int(b)] for a, b in pairs if a != b]


def collective_stats(txt: str, *, model: int = 1, data: int = 1,
                     node: int = 1) -> list[Collective]:
    """One :class:`Collective` record per collective instruction in the
    compiled module text (async start/done pairs counted once, at start)."""
    comps, _ = parse_computations(txt)
    out: list[Collective] = []
    for cname, lines in comps.items():
        for result_type, kind, operands, attrs in split_ops(lines):
            base = kind[:-6] if kind.endswith("-start") else kind
            if base not in _KINDS or kind.endswith("-done"):
                continue
            if base == "collective-permute":
                groups = _permute_groups(attrs)
                gsize = 2 if groups else 1
            else:
                groups = _parse_replica_groups(attrs)
                gsize = max((len(g) for g in groups), default=1)
            operand_b = shape_bytes(operands)
            result_b = shape_bytes(result_type)
            if kind.endswith("-start"):      # result repeats the operand
                result_b = max(result_b - operand_b, operand_b)
            axis, fabric = _classify(groups, model, data, node)
            cid = re.search(r"channel_id=(\d+)", attrs)
            out.append(Collective(
                kind=base, payload_bytes=operand_b, result_bytes=result_b,
                wire_bytes=_wire_bytes(base, gsize, operand_b, result_b),
                group_size=gsize, n_groups=len(groups), axis=axis,
                fabric=fabric, channel_id=int(cid.group(1)) if cid else None,
                computation=cname, replica_groups=groups,
                tensor_bytes=tuple(shape_bytes(f"{t}[{d}]") for t, d in
                                   _SHAPE_RE.findall(operands)
                                   if t in _DTYPE_BYTES)))
    return out


# ---------------------------------------------------------------------------
# aggregation (JSON-serializable, for dryrun records / TrainReport)
# ---------------------------------------------------------------------------


def summarize(colls: list[Collective]) -> dict:
    """Aggregate collectives by kind: counts and trip-weighted bytes."""
    by_kind: dict[str, dict] = {}
    for c in colls:
        d = by_kind.setdefault(c.kind, {"count": 0, "payload_bytes": 0,
                                        "wire_bytes": 0.0})
        d["count"] += c.trips
        d["payload_bytes"] += c.payload_bytes * c.trips
        d["wire_bytes"] += c.weighted_wire_bytes
    return {
        "by_kind": by_kind,
        "total_count": sum(d["count"] for d in by_kind.values()),
        "total_wire_bytes": sum(d["wire_bytes"] for d in by_kind.values()),
    }


def axis_bytes(colls: list[Collective]) -> dict[str, float]:
    """Trip-weighted wire bytes per fabric tier (tp / intra_node /
    inter_node / inter_pod) — the Fig. 6 measured counterpart of
    ``plan_bytes``."""
    out: dict[str, float] = {}
    for c in colls:
        out[c.fabric] = out.get(c.fabric, 0.0) + c.weighted_wire_bytes
    return out


def internode_bytes(colls: list[Collective]) -> float:
    """Total bytes crossing a node or pod boundary (the slow fabrics;
    mixed-stride groups classify as inter_node)."""
    ab = axis_bytes(colls)
    return ab.get("inter_node", 0.0) + ab.get("inter_pod", 0.0)


def as_records(colls: list[Collective]) -> list[dict]:
    """Plain-dict dump (replica groups elided) for JSON reports."""
    out = []
    for c in colls:
        d = asdict(c)
        d.pop("replica_groups", None)
        out.append(d)
    return out
