"""From a profiler trace of the window to the numbers the metrics read.

Two stages.  ``extract`` reads the ``.xplane.pb`` the JAX profiler wrote
and keeps what the reduction needs, as plain lists: each TPU's operations
from its ``XLA Ops`` line, each classed by the compiled round's HLO text
(``classify``), the collectives of its ``Async XLA Ops`` line, and the
host annotations of the benchmark's loop (``bench.*``).  The reductions
below work on that extract only, so they are checked on a small extract
recorded from a chip run (``testdata/``).

On a TPU an event of the ``XLA Ops`` line is named by its whole HLO
instruction (``%fusion.45 = f32[...] fusion(...)``), and its category is
not in the event's stats.  So the class comes from the HLO text: ``conv``
for a convolution or a fusion that holds one, ``prox`` for a Pallas call
(``tpu_custom_call``; the round's only Pallas kernel is the fused
prox-SGD update), ``coll`` for a collective, ``op`` for the rest.  Events
nest: a ``while`` spans the ops of its body.
"""
from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")
HOST_PREFIX = "bench."
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=\{?%?([\w.\-]+)")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's right-hand side (after its type)."""
    depth, i = 0, 0
    for i, ch in enumerate(rest):        # skip a tuple type's parentheses
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    m = _OPCODE.search(" " + rest[i:].lstrip())
    return m.group(1) if m else ""


def classify(hlo_text: str) -> dict:
    """{instruction name: class} for every instruction of the module."""
    ops, calls, comp_of = {}, {}, {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or cur is None:
            continue
        name, rest = m.group(1), m.group(2)
        op = _opcode(rest)
        if op == "custom-call" and '"tpu_custom_call"' in rest:
            op = "pallas"
        ops[name] = op
        calls[name] = _CALLS.findall(rest)
        comp_of.setdefault(cur, []).append(name)

    memo = {}

    def has_conv(comp, seen=()):
        if comp in memo:
            return memo[comp]
        if comp in seen:
            return False
        found = any(ops[i] == "convolution" or any(
            has_conv(c, seen + (comp,)) for c in calls[i])
            for i in comp_of.get(comp, ()))
        memo[comp] = found
        return found

    out = {}
    for name, op in ops.items():
        base = re.sub(r"-(start|done)$", "", op)
        if op == "pallas":
            out[name] = "prox"
        elif base in COLLECTIVES:
            out[name] = "coll"
        elif op == "convolution" or (op == "fusion" and any(
                has_conv(c) for c in calls[name])):
            out[name] = "conv"
        else:
            out[name] = "op"
    return out


def _instr_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.lstrip("%").split(" ")[0]


def extract(trace_dir: str, hlo_text: str) -> dict:
    """The device operations, async collectives and host annotations of
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    kinds = classify(hlo_text)
    data = ProfileData.from_file(paths[-1])
    devices, async_, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops, coll = [], []
            for ln in plane.lines:
                if ln.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                for e in ln.events:
                    name = _instr_name(e.name)
                    kind = kinds.get(name, "op")
                    if ln.name == "XLA Ops":
                        ops.append([kind, name, int(e.start_ns),
                                    int(e.duration_ns)])
                    elif kind == "coll":
                        coll.append([kind, name, int(e.start_ns),
                                     int(e.duration_ns)])
            devices[plane.name] = ops
            async_[plane.name] = coll
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"devices": devices, "async": async_,
            "host": sorted(host, key=lambda h: h[1])}


def window_of(ex: dict) -> tuple[int, int]:
    """The traced window: first to last host annotation of the loop."""
    if not ex["host"]:
        raise ValueError("no bench.* host annotation in the trace")
    return (ex["host"][0][1], max(s + d for _, s, d in ex["host"]))


def clip(ex: dict, start_ns: int, end_ns: int) -> dict:
    """The extract cut to ``[start_ns, end_ns)``: events that overlap it,
    trimmed to it."""
    def cut(rows, s_at):
        out = []
        for r in rows:
            s, d = r[s_at], r[s_at + 1]
            a, b = max(s, start_ns), min(s + d, end_ns)
            if b > a:
                out.append(r[:s_at] + [a, b - a])
        return out
    return {"devices": {k: cut(v, 2) for k, v in ex["devices"].items()},
            "async": {k: cut(v, 2) for k, v in ex.get("async", {}).items()},
            "host": cut(ex["host"], 1), "window": [start_ns, end_ns]}


def busy_intervals(ops) -> list[tuple[int, int]]:
    """The union of the events' intervals, as sorted disjoint
    ``(start, end)`` pairs."""
    out: list[list[int]] = []
    for _, _, s, d in sorted(ops, key=lambda o: o[2]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def busy_ns(ops) -> int:
    return sum(b - a for a, b in busy_intervals(ops))


def kind_ns(ops, kind: str) -> int:
    """Summed device time of the events of one class."""
    return sum(o[3] for o in ops if o[0] == kind)


def self_ns(ops) -> list[int]:
    """Each event's own time: its duration less that of the events it
    directly holds (a ``while`` holds its body's operations)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [o[3] for o in ops]
    stack: list[int] = []
    for i in order:
        s = ops[i][2]
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][3]
        stack.append(i)
    return own


def top_ops(ex: dict, n: int = 10) -> list:
    """The ``n`` operations, by class and name with the instruction number
    dropped, that took the most device time of their own, in seconds
    averaged over the devices."""
    tot: dict = {}
    for ops in ex["devices"].values():
        for o, own in zip(ops, self_ns(ops)):
            key = f"{o[0]}:{re.sub(r'[.]\d+$', '', o[1])}"
            tot[key] = tot.get(key, 0) + own
    k = max(len(ex["devices"]), 1)
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda t: -t[1])[:n]]


def idle_gaps(ex: dict, n: int = 10) -> list:
    """The ``n`` longest stretches in which the first device ran nothing,
    each named by the host annotation that covers its middle
    (``host.other`` where none does), in seconds."""
    devs = sorted(ex["devices"])
    if not devs:
        return []
    start, end = ex["window"]
    gaps, cur = [], start
    for a, b in busy_intervals(ex["devices"][devs[0]]):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if end > cur:
        gaps.append((cur, end))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        who = "host.other"
        for name, s, d in ex["host"]:
            if s <= mid < s + d:
                who = name
        out.append([who, (b - a) / 1e9])
    return out
