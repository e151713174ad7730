"""The readers of the program's own names (``benchmarks/chip/scopes.py``):
the scope of each HLO instruction, the device time per scope, the idle
gaps beside the host spans, and the feed's span counters over the
untraced part of a window; on hand-made inputs, on a smoke round
compiled on the CPU, and in a traced run of the harness on the CPU."""
import re
import time

import jax
import pytest

from bench_smoke import smoke_cell

from benchmarks.chip import cells, peaks, run, scopes, tracing

SEED = 2**31 + 1313
MS = 1_000_000      # ns


def _meta(scope):
    return f', metadata={{op_name="jit(step)/{scope}/x" source_line=1}}'


_ROUND = f"""\
HloModule round

%fused_conv (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {{
  %p0 = f32[8,8]{{1,0}} parameter(0)
  %p1 = f32[8,8]{{1,0}} parameter(1)
  %convolution.3 = f32[8,8]{{1,0}} convolution(%p0, %p1), dim_labels=bf_io->bf{_meta("while/body/local/model")}
  ROOT %m = f32[8,8]{{1,0}} multiply(%convolution.3, %p0){_meta("while/body/transpose(jvp(local/model))")}
}}

%fused_multi (p0: f32[8,8]) -> (f32[8,8], f32[8,8]) {{
  %p0 = f32[8,8]{{1,0}} parameter(0)
  %a = f32[8,8]{{1,0}} add(%p0, %p0){_meta("consensus/level1/consensus/masks")}
  %b = f32[8,8]{{1,0}} multiply(%p0, %p0)
  ROOT %mt = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}) tuple(%b, %a)
}}

ENTRY %main (x: f32[8,8]) -> (f32[8,8], f32[8,8]) {{
  %x = f32[8,8]{{1,0:T(8,128)}} parameter(0)
  %fusion.1 = f32[8,8]{{1,0:T(8,128)}} fusion(%x, %x), kind=kOutput, calls=%fused_conv{_meta("consensus/update")}
  %fusion.2 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}) fusion(%x), kind=kLoop, calls=%fused_multi
  %prox_sgd.7 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}) custom-call(%x, %x), custom_call_target="tpu_custom_call"{_meta("while/body/closed_call/local/update/prox_sgd")}
  %reshape.5 = f32[64]{{0}} reshape(%copy.9){_meta("local/update")}
  %all-reduce-start.4 = f32[8,8]{{1,0}} all-reduce-start(%x), replica_groups={{{{0,1}}}}, to_apply=%fused_conv{_meta("consensus/level2")}
  %all-reduce-done.4 = f32[8,8]{{1,0}} all-reduce-done(%all-reduce-start.4){_meta("consensus/level2")}
  %add.10 = f32[8,8]{{1,0}} add(%x, %x){_meta("consensus/level10")}
  %copy.9 = f32[8,8]{{0,1}} copy(%x)
  ROOT %t = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}) tuple(%all-reduce-done.4, %fusion.1)
}}
"""


def test_scope_of_hand_written_hlo():
    s = scopes.scope_of(_ROUND)
    # a fusion takes its fused root's scope, the backward pass's too
    assert s["fusion.1"] == s["convolution.3"] == "local/model"
    # a tuple root: the first of its operands with a scope; the innermost
    # scope of a nested name
    assert s["fusion.2"] == "consensus/masks"
    assert s["prox_sgd.7"] == s["reshape.5"] == "local/update"
    assert s["all-reduce-start.4"] == "consensus/level2"
    assert s["add.10"] == "consensus/level10"
    assert s["copy.9"] == s["x"] == scopes.UNSCOPED
    assert tracing.classify(_ROUND)["prox_sgd.7"] == "prox"
    # an unscoped copy goes with the phase of its nearest scoped user
    near = scopes.nearest_scope(_ROUND, s)
    assert near["copy.9"] == "local/update" and near["x"] == "local/model"


def _ops(masks_ms):
    """A traced round on one chip (times in ms): a scan holding the model,
    the prox kernel and a masks fusion, then an exchange, a copy and
    another model fusion."""
    rows = [["op", "while.1", 0, 100],
            ["conv", "fusion.1", 0, 40],
            ["prox", "prox_sgd.7", 40, 10],
            ["op", "fusion.2", 50, masks_ms],
            ["coll", "all-reduce-start.4", 100, 20],
            ["op", "copy.9", 150, 10],
            ["conv", "fusion.1", 200, 40]]
    return [[k, n, s * MS, d * MS] for k, n, s, d in rows]


def _ctx(hlo=_ROUND):
    return {"hlo_text": hlo, "traced": {"rounds": 2},
            "trace": {"devices": {"/device:TPU:0": _ops(30),
                                  "/device:TPU:1": _ops(10)},
                      "host": [["bench.dispatch", 0, 60 * MS],
                               ["bench.input_next", 60 * MS, 200 * MS]],
                      "window": [0, 250 * MS]}}


def test_device_readers_on_a_hand_made_extract(capsys):
    """Worked by hand: per chip, own times model 80, update 10, masks
    30 / 10, level2 20, unscoped 30 / 50 (the scan's own 20 / 40 and the
    copy); busy 170.  Mean over two chips, over two rounds."""
    ops = _ops(30)
    own = scopes.scope_ns(ops, scopes.scope_of(_ROUND))
    assert own == {"local/model": 80 * MS, "local/update": 10 * MS,
                   "consensus/masks": 30 * MS, "consensus/level2": 20 * MS,
                   scopes.UNSCOPED: 30 * MS}
    assert sum(own.values()) == tracing.busy_ns(ops) == 170 * MS
    ctx = _ctx()
    want = {"local.model_ms_per_round": 40.0,
            "local.update_ms_per_round": 5.0,
            "consensus.masks_ms_per_round": 10.0,
            "consensus.ms_per_round": 20.0}
    for name, value in want.items():
        read, unit, kind = cells.metric_reader(name)
        assert (unit, kind) == ("ms", "per_layer")
        assert read(ctx) == pytest.approx(value), name
    err = capsys.readouterr().err
    assert "sum=85.000 busy=85.000" in err
    assert "[scopes] unscoped top: while=15.000 copy=5.000" in err
    assert "[scopes] unscoped by its nearest scoped user or operand: " \
        "local/update=5.000 unscoped=15.000" in err
    assert "[gaps]" in err


def test_device_readers_are_silent_without_scopes():
    """A program from before the scopes: every instruction unscoped, so
    no device reader reports, and none raises."""
    bare = re.sub(r", metadata=\{[^}]*\}", "", _ROUND)
    assert set(scopes.scope_of(bare).values()) == {scopes.UNSCOPED}
    for name in ("local.model_ms_per_round", "local.update_ms_per_round",
                 "consensus.masks_ms_per_round", "consensus.ms_per_round"):
        read, _, _ = cells.metric_reader(name)
        assert read(_ctx(bare)) is None
        assert read({"traced": {"rounds": 1}, "trace": None}) is None


def test_masks_reader_is_absent_where_the_round_makes_no_masks():
    frozen = _ROUND.replace("consensus/level1/consensus/masks",
                            "consensus/level1")
    read, _, _ = cells.metric_reader("consensus.masks_ms_per_round")
    assert read(_ctx(frozen)) is None
    read, _, _ = cells.metric_reader("consensus.ms_per_round")
    assert read(_ctx(frozen)) == pytest.approx(20.0)


def test_gaps_name_the_loop_and_the_feed_thread():
    ex = _ctx()["trace"]
    spans = [["repro.feed.produce", "feed", 100 * MS, 120 * MS],
             ["repro.feed.stack", "feed", 130 * MS, 60 * MS],
             ["repro.feed.get_wait", "main", 120 * MS, 80 * MS]]
    got = scopes.gaps(ex, spans)
    # gaps of the first chip: 160-200, 120-150, 240-250
    assert got == [[40e-3, "bench.input_next", "repro.feed.stack"],
                   [30e-3, "bench.input_next", "repro.feed.stack"],
                   [10e-3, "bench.input_next", "none"]]


def test_feed_readers_read_the_untraced_part():
    from repro.dist import monitor
    with monitor.span("repro.feed.produce"):      # before: not read
        time.sleep(0.03)
    resumed = time.perf_counter()
    for _ in range(4):
        with monitor.span("repro.feed.produce"):
            with monitor.span("repro.feed.stack"):
                time.sleep(0.01)
    end = time.perf_counter()
    ctx = {"window": {"rounds": 4, "seconds": end - resumed,
                      "input_wait_s": 0.0},
           "traced": {"rounds": 1, "resumed": resumed}}
    produce, _, _ = cells.metric_reader("feed.produce_ms_per_round")
    stack, _, _ = cells.metric_reader("feed.stack_ms_per_round")
    assert 10.0 <= stack(ctx) <= produce(ctx) < 25.0
    assert ctx["feed"]["repro.feed.produce"][0] == 1.0


def test_feed_readers_are_silent_without_the_counters(monkeypatch):
    from repro.dist import monitor
    monkeypatch.delattr(monitor, "span_totals")
    ctx = {"window": {"rounds": 4, "seconds": 1.0, "input_wait_s": 0.0},
           "traced": {"rounds": 1, "resumed": time.perf_counter()}}
    read, _, _ = cells.metric_reader("feed.produce_ms_per_round")
    assert read(ctx) is None


def test_scope_of_a_smoke_round_compiled_on_the_cpu(monkeypatch):
    """Every convolution is the model's, the prox kernel (interpreted
    here) the update's, the mask sync's sort and top-k the masks', and
    every level of the engine has its own scope."""
    from benchmarks.chip import session
    cell, _ = smoke_cell("resnet18.dynamic", monkeypatch)
    sess = session.Session(cell, jax.devices(), SEED)
    hlo = sess.hlo_text
    levels = sess.engine.consensus.levels
    sess.free()
    s = scopes.scope_of(hlo)
    ops, names = {}, {}
    for line in hlo.splitlines():
        m = tracing._INSTR.match(line)
        if m:
            ops[m.group(1)] = tracing._opcode(m.group(2))
            if 'custom_call_target="TopK"' in line:
                ops[m.group(1)] = "top-k"
            n = re.search(r'op_name="([^"]*)"', line)
            names[m.group(1)] = n.group(1) if n else ""
    convs = [n for n, op in ops.items() if op == "convolution"]
    assert convs and {s[n] for n in convs} == {"local/model"}
    prox = [n for n in ops if "/prox_sgd" in names[n]]
    assert prox and {s[n] for n in prox} == {"local/update"}
    sorts = [n for n, op in ops.items() if op in ("sort", "top-k")]
    assert sorts and {s[n] for n in sorts} == {"consensus/masks"}
    assert {f"consensus/level{k}" for k in range(1, len(levels) + 1)} \
        <= set(s.values())
    assert {"local/model", "local/update", "consensus/masks",
            "consensus/update", scopes.UNSCOPED} <= set(s.values())


def test_traced_run_on_the_cpu_reads_the_feed_counters(monkeypatch, capsys):
    """The harness's traced run at smoke size: the feed readers report,
    and the consumer's wait in the program's counter agrees with the
    loop's own clock."""
    cell, _ = smoke_cell("resnet18.dynamic", monkeypatch)
    monkeypatch.setattr(peaks, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    res = run.run_cell(cell, SEED, 2.5, True, jax.devices(),
                       time.perf_counter())
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["feed.produce_ms_per_round"]["value"] > 0
    assert 0 < m["feed.stack_ms_per_round"]["value"] \
        <= m["feed.produce_ms_per_round"]["value"]
    err = capsys.readouterr().err
    feed = re.search(r"\[feed\] .*get_wait=([\d.]+)ms.*"
                     r"host.input_wait_ms_per_round=([\d.]+)", err)
    get_wait, input_wait = float(feed.group(1)), float(feed.group(2))
    assert abs(get_wait - input_wait) <= max(0.05 * input_wait, 1.0)
    assert input_wait == pytest.approx(
        m["host.input_wait_ms_per_round"]["value"], abs=1e-3)


def _sweep_scope_ns(ops, scope_map):
    """Device time per scope by a sweep over the events' edges: each
    stretch between two edges belongs to the innermost running event,
    the one started last (then the shortest)."""
    import heapq
    events = sorted(ops, key=lambda o: o[2])
    edges = sorted({o[2] for o in ops} | {o[2] + o[3] for o in ops})
    running, i, out = [], 0, {}
    for a, b in zip(edges, edges[1:]):
        while i < len(events) and events[i][2] <= a:
            o = events[i]
            heapq.heappush(running, (-o[2], o[3], i, o))
            i += 1
        live = [r for r in running if r[3][2] + r[3][3] > a]
        if live:
            s = scope_map.get(min(live)[3][1], scopes.UNSCOPED)
            out[s] = out.get(s, 0) + b - a
    return out


def test_reduction_on_a_recorded_chip_trace_with_scopes():
    """An 80 ms cut of a traced resnet18.dynamic window on a v5e chip,
    with the scope of each instruction that ran in it (from the round's
    compiled HLO) and the program's host spans.  The recording named both
    Python threads alike, so the spans' threads were set by name here:
    the consumer's ``get_wait`` on ``python3#0``, the feed's others on
    ``python3#1``.  The times per scope are checked against a sweep made
    apart from the library."""
    import gzip
    import json
    import os
    from bench_smoke import ROOT
    path = os.path.join(ROOT, "benchmarks", "chip", "testdata",
                        "resnet18.dynamic.scopes_extract.json.gz")
    with gzip.open(path, "rt") as f:
        ex = json.load(f)
    ops = ex["devices"]["/device:TPU:0"]
    assert len(ops) == 8472
    assert ex["window"][1] - ex["window"][0] == 80_000_000
    own = scopes.scope_ns(ops, ex["scopes"])
    assert own == _sweep_scope_ns(ops, ex["scopes"])
    assert own == {"local/model": 55_001_962, "local/update": 4_645_840,
                   "consensus/masks": 259_655, "consensus/level1": 572_444,
                   "consensus/level2": 1_275_356,
                   "consensus/update": 1_506_052,
                   scopes.UNSCOPED: 8_620_714}
    assert sum(own.values()) == tracing.busy_ns(ops) == 71_882_023
    # the prox kernel is named, and is the update's
    assert {ex["scopes"][o[1]] for o in ops
            if o[1].startswith("prox_sgd")} == {"local/update"}
    assert {s[0] for s in ex["spans"]} == set(scopes.FEED)
    assert scopes.gaps(ex, ex["spans"], 2) == [
        [0.005655957, "none", "repro.feed.load"],
        [0.000731982, "bench.drain", "repro.feed.stack"]]
