"""The round's device time by the program's own names, and what the host
threads did meanwhile.

The program names the phases of a round with ``jax.named_scope``
(``core/hsadmm.py``, ``core/consensus.py``): each HLO instruction carries
the scope in its ``op_name`` metadata, the backward pass as
``transpose(jvp(<scope>))``.  ``scope_of`` maps every instruction of the
compiled round to the innermost of these scopes found there:

    local/model         forward and backward pass of the local step
    local/update        the per-leaf prox-SGD update (the Pallas kernel,
                        the (R, C) views around it, z1's broadcast)
    consensus/masks     mask scoring, sync and drift
    consensus/level<k>  level k's payload, compaction, exchange, expansion
    consensus/update    duals, penalties and residuals

A fusion takes the scope of its fused computation's root.  An instruction
with none (a copy that layout assignment inserted, the scan's own loop)
is ``unscoped``, and so is an event of another program.

The host names the stages of its work with ``dist.monitor.span``: each is
a profiler annotation (``repro.*``, on the host plane of the trace) and a
counter.  ``host_spans`` reads the annotations with their threads,
``gaps`` puts the device's longest idle gaps beside what the loop and
the feed thread were doing, and ``feed_per_round`` reads the counters
over the part of the window after the profiler stopped.

Every reader returns None where the program has no such scope or span,
as a program from before they existed has not.
"""
from __future__ import annotations

import glob
import os
import re
import sys

from benchmarks.chip import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")   # run.py's trace directory
UNSCOPED = "unscoped"
SPAN_PREFIX = "repro."
FEED = ("repro.feed.load", "repro.feed.stack", "repro.feed.produce",
        "repro.feed.put_wait", "repro.feed.get_wait")
_SCOPE = re.compile(r"(local/model|local/update|consensus/masks|"
                    r"consensus/level\d+|consensus/update)(?![\w])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def _scope_in(op_name: str) -> str | None:
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def scope_of(hlo_text: str) -> dict:
    """{instruction name: scope} for every instruction of the module."""
    own, op, calls, args, root = {}, {}, {}, {}, {}
    cur = None
    for line in hlo_text.splitlines():
        m = tracing._COMP.match(line)
        if m:
            cur = m.group(1)
            continue
        m = tracing._INSTR.match(line)
        if not m or cur is None:
            continue
        name, rest = m.group(1), m.group(2)
        meta = _OP_NAME.search(rest)
        own[name] = _scope_in(meta.group(1)) if meta else None
        op[name] = tracing._opcode(rest)
        calls[name] = tracing._CALLS.findall(rest)
        if op[name] == "tuple":
            args[name] = _OPERAND.findall(
                rest.split(" tuple(", 1)[-1].split("metadata=", 1)[0])
        if line.lstrip().startswith("ROOT"):
            root[cur] = name

    memo: dict = {}

    def resolve(name, depth=0):
        if name in memo:
            return memo[name]
        found = None
        if depth < 16:
            if op.get(name) == "fusion" and calls[name]:
                found = resolve(root.get(calls[name][0]), depth + 1)
            elif name in args and not own.get(name):
                found = next((s for s in (resolve(a, depth + 1)
                                          for a in args[name]) if s), None)
        found = found or own.get(name)
        memo[name] = found
        return found

    return {name: resolve(name) or UNSCOPED for name in op}


def nearest_scope(hlo_text: str, scopes: dict, depth: int = 6) -> dict:
    """{unscoped instruction: the scope of its nearest scoped user, else
    of its nearest scoped operand, within ``depth`` steps}, for putting
    the copies and collectives that the compiler inserted beside the
    phase they serve (``unscoped`` where none is that near)."""
    comps, users, operands = set(), {}, {}
    for line in hlo_text.splitlines():
        m = tracing._COMP.match(line)
        if m:
            comps.add(m.group(1))
            continue
        m = tracing._INSTR.match(line)
        if m:
            body = m.group(2).split("metadata=", 1)[0]
            operands[m.group(1)] = _OPERAND.findall(body)
    for name, args in operands.items():
        args[:] = [a for a in args if a not in comps and a in operands]
        for a in args:
            users.setdefault(a, []).append(name)

    def walk(start, edges):
        seen, front = {start}, [start]
        for _ in range(depth):
            nxt = []
            for n in front:
                for e in edges.get(n, ()):
                    if e in seen:
                        continue
                    if scopes.get(e, UNSCOPED) != UNSCOPED:
                        return scopes[e]
                    seen.add(e)
                    nxt.append(e)
            front = nxt
        return None

    return {n: walk(n, users) or walk(n, operands) or UNSCOPED
            for n, sc in scopes.items() if sc == UNSCOPED}


def scope_ns(ops, scopes: dict) -> dict:
    """{scope: device time of the events' own (``tracing.self_ns``)}."""
    out: dict = {}
    for o, ns in zip(ops, tracing.self_ns(ops)):
        s = scopes.get(o[1], UNSCOPED)
        out[s] = out.get(s, 0) + ns
    return out


def round_hlo(ctx: dict) -> str | None:
    """The compiled round's HLO text.  ``run.py`` hands its readers none,
    so it is read from ``run_cell``'s local ``hlo_text`` on the stack."""
    if "hlo_text" in ctx:
        return ctx["hlo_text"]
    f = sys._getframe(1)
    while f is not None and not (f.f_code.co_name == "run_cell"
                                 and "hlo_text" in f.f_locals):
        f = f.f_back
    ctx["hlo_text"] = f.f_locals["hlo_text"] if f is not None else None
    return ctx["hlo_text"]


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def round_scopes(ctx: dict) -> dict | None:
    """{scope: device ms per traced round, averaged over the chips}, or
    None where the trace or the program's scopes are missing.  The first
    call prints ``[scopes]`` and ``[gaps]`` on standard error."""
    if "scopes" in ctx:
        return ctx["scopes"]
    ctx["scopes"] = None
    tr = ctx.get("trace")
    hlo = round_hlo(ctx)
    if not tr or not tr["devices"] or not hlo:
        return None
    scopes = scope_of(hlo)
    near = nearest_scope(hlo, scopes)
    devs = sorted(tr["devices"])
    per = 1e6 * ctx["traced"]["rounds"] * len(devs)     # ns -> ms per round

    def ms(label):
        """{label: ms per round} with each event labelled by name."""
        out: dict = {}
        for d in devs:
            ops = tr["devices"][d]
            names = {o[1]: label(o[1]) for o in ops}
            for k, ns in scope_ns(ops, names).items():
                out[k] = out.get(k, 0) + ns / per
        return out
    tot = ms(lambda n: scopes.get(n, UNSCOPED))
    top = ms(lambda n: (scopes.get(n, UNSCOPED), re.sub(r"[.]\d+$", "", n)))
    unscoped = ms(lambda n: near.get(n, UNSCOPED)
                  if scopes.get(n, UNSCOPED) == UNSCOPED else None)
    unscoped.pop(None, None)
    busy = sum(tracing.busy_ns(tr["devices"][d]) for d in devs) / per
    _log(f"[scopes] ms per traced round, mean of {len(devs)} chip(s): "
         + " ".join(f"{s}={v:.3f}" for s, v in sorted(tot.items()))
         + f" sum={sum(tot.values()):.3f} busy={busy:.3f}")
    for s in sorted(tot):
        rows = sorted(((k, v) for (t, k), v in top.items() if t == s),
                      key=lambda r: -r[1])[:3]
        _log(f"[scopes] {s} top: "
             + " ".join(f"{k}={v:.3f}" for k, v in rows))
    _log("[scopes] unscoped by its nearest scoped user or operand: "
         + " ".join(f"{s}={v:.3f}" for s, v in sorted(unscoped.items())))
    spans = host_spans(TRACE_DIR, tr["window"])
    for sec, loop, feed in gaps(tr, spans):
        _log(f"[gaps] {sec * 1e3:.3f} ms loop={loop} feed={feed}")
    if any(s != UNSCOPED for s in tot):
        ctx["scopes"] = tot
    return ctx["scopes"]


def ms_per_round(ctx: dict, prefix: str) -> float | None:
    """Device ms per traced round under every scope starting ``prefix``;
    None where the round has none."""
    ms = round_scopes(ctx)
    if ms is None:
        return None
    hits = [v for s, v in ms.items() if s.startswith(prefix)]
    return sum(hits) if hits else None


def host_spans(trace_dir: str, window=None) -> list:
    """``[name, thread, start_ns, duration_ns]`` of every ``repro.*``
    host annotation in the newest ``.xplane.pb`` under ``trace_dir``,
    cut to ``window`` when given.  A thread is a line of a host plane,
    ``<line name>#<index>``: Python's threads share one line name."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    rows.append([e.name, f"{ln.name}#{i}", int(e.start_ns),
                                 int(e.duration_ns)])
    if window is not None:
        a, b = window
        rows = [r for r in rows if r[2] < b and r[2] + r[3] > a]
    return sorted(rows, key=lambda r: r[2])


def gaps(ex: dict, spans: list, n: int = 10) -> list:
    """The ``n`` longest stretches in which the first device ran nothing,
    ``[seconds, loop, feed]``: the ``bench.*`` annotation of the loop
    over the gap's middle and the innermost ``repro.feed.*`` span of the
    feed thread there (``none`` where nothing covers it)."""
    devs = sorted(ex["devices"])
    if not devs:
        return []
    start, end = ex["window"]
    found, cur = [], start
    for a, b in tracing.busy_intervals(ex["devices"][devs[0]]):
        if a > cur:
            found.append((cur, a))
        cur = max(cur, b)
    if end > cur:
        found.append((cur, end))
    feed_threads = {r[1] for r in spans if r[0] == "repro.feed.produce"}
    out = []
    for a, b in sorted(found, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        loop = [h for h in ex["host"] if h[1] <= mid < h[1] + h[2]]
        feed = [r for r in spans if r[1] in feed_threads
                and r[2] <= mid < r[2] + r[3]]
        out.append([(b - a) / 1e9,
                    min(loop, key=lambda h: h[2])[0] if loop else "none",
                    min(feed, key=lambda r: r[3])[0] if feed else "none"])
    return out


def feed_per_round(ctx: dict) -> dict | None:
    """{feed span: [count, ms] per round} from the program's span
    counters over the part of the window after the profiler stopped,
    or None without such a part or such counters.  The first call
    prints ``[feed]`` on standard error."""
    if "feed" in ctx:
        return ctx["feed"]
    ctx["feed"] = None
    from repro.dist import monitor
    w, traced = ctx["window"], ctx.get("traced") or {}
    totals = getattr(monitor, "span_totals", None)
    if totals is None or not w["rounds"] or "resumed" not in traced:
        return None
    since = traced["resumed"]
    got = totals(since=since, until=since + w["seconds"])
    per = {n: [got[n].count / w["rounds"], 1e3 * got[n].seconds / w["rounds"]]
           for n in FEED if n in got}
    if not per:
        return None
    _log(f"[feed] per round over {w['rounds']} untraced rounds: " + " ".join(
        f"{n[len('repro.feed.'):]}={ms:.3f}ms/{c:.2f}"
        for n, (c, ms) in per.items())
        + f" host.input_wait_ms_per_round="
        f"{1e3 * w['input_wait_s'] / w['rounds']:.3f}")
    ctx["feed"] = per
    return per


def feed_ms_per_round(ctx: dict, name: str) -> float | None:
    per = feed_per_round(ctx)
    return per[name][1] if per and name in per else None
