"""The chip benchmark's yardstick on the CPU: the FLOP and byte functions, the peaks table, the harness finding every
cell, configuration, traffic mix and metric by name, and a run without a
chip exiting non-zero with no result."""
import gzip
import json
import os
import subprocess
import sys

import jax
import pytest

from bench_smoke import ROOT

from benchmarks.chip import cells, check, flops, peaks

TESTDATA = os.path.join(ROOT, "benchmarks", "chip", "testdata")
R18 = cells.load("resnet18.dynamic")["config"]["arch"]
W50 = cells.load("wideresnet50-2.dynamic")["config"]["arch"]


# --------------------------------------------------------------------------
# FLOPs, bytes, peaks
# --------------------------------------------------------------------------


@pytest.mark.parametrize("arch,registry,macs,params", [
    (R18, "resnet18", 555_422_720, 11_173_962),
    (W50, "wideresnet50-2", 3_684_388_864, 66_847_050),
])
def test_model_macs(arch, registry, macs, params):
    from repro.configs import get_config
    from repro.models import build
    assert flops.model_macs(arch) == (macs, params)
    bundle = build(get_config(registry))
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params
    assert flops.train_flops_per_image(arch) == 6 * macs


def test_reconfigured_widths_and_macs():
    from benchmarks.chip.reference.hsadmm import reconfigured_arch
    rc = reconfigured_arch(R18, 0.5)
    assert (rc["stem"], rc["outs"], rc["cmid"]) == \
        (32, [32, 64, 128, 256], [32, 64, 128, 256])
    macs, _ = flops.model_macs(rc)
    assert macs < flops.model_macs(R18)[0] / 3.5


def test_prox_bytes():
    # 5 float32 reads and 2 writes of every parameter of every worker
    assert flops.prox_bytes_per_step(R18, 4) == 7 * 4 * 4 * 11_173_962
    assert flops.prox_bytes_per_step(W50, 1) == 28 * 66_847_050


def test_peaks_known_and_unknown():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# --------------------------------------------------------------------------
# the harness finds everything by name
# --------------------------------------------------------------------------


def _benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_config_traffic_and_metric_loads():
    bench = _benchmark_json()
    listed = {w["name"]: w for w in bench["workloads"]}
    assert set(listed) <= set(cells.names("workloads"))
    for name in cells.names("workloads"):
        cell = cells.load(name)
        assert cell["limits"] and set(cell["limits"]) <= set(check.NUMBERS)
        w = listed.get(name)
        if w is not None:
            assert cell["config"]["name"] == w["config"]
            assert cell["traffic"]["name"] == w["traffic"]
            assert cell["chips"] == w["chips"]
    for c in bench["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
    kinds = {}
    for name in cells.names("metrics"):
        read, unit, kind = cells.metric_reader(name)
        assert callable(read)
        kinds[name] = (unit, kind)
    for m in bench["end_to_end"]:
        assert kinds[m["name"]] == (m["unit"], "end_to_end")
    for m in bench["per_layer"]:
        assert kinds[m["name"]] == (m["unit"], "per_layer")
    # a run reports every end-to-end reader, so each must be listed
    assert {n for n, (_, k) in kinds.items() if k == "end_to_end"} == \
        {m["name"] for m in bench["end_to_end"]}


def test_host_clock_metrics_read_the_untraced_part():
    """In a traced run the host-clock metrics read the window after the
    profiler stopped: its rounds, seconds and input wait."""
    from benchmarks.chip import run
    win = {"rounds": 50, "seconds": 10.0, "t_start": 100.0,
           "input_wait_s": 5.0}
    traced = {"rounds": 10, "seconds": 2.5, "input_wait_s": 1.5,
              "resumed": 103.0}
    host = run.untraced_part(win, traced)
    assert host == pytest.approx({"rounds": 40, "seconds": 7.0,
                                  "input_wait_s": 3.5})
    ctx = {"window": host, "images_per_round": 100, "flops_per_image": 1e9,
           "chips": 1, "peaks": {"bf16_flops": 1e12}}
    mfu, _, _ = cells.metric_reader("mfu")
    wait, _, _ = cells.metric_reader("host.input_wait_ms_per_round")
    assert mfu(ctx) == pytest.approx(100 * 40 * 100 * 1e9 / (7.0 * 1e12))
    assert wait(ctx) == pytest.approx(1e3 * 3.5 / 40)
    ctx["window"] = run.untraced_part(win, dict(win, resumed=110.0))
    assert mfu(ctx) is None and wait(ctx) is None


def test_window_runs_on_to_the_deadline_a_drain_sets():
    """A traced run's drain hook stops the profiler and moves the window's
    deadline, so the untraced part lasts its whole length."""
    import time
    from types import SimpleNamespace

    import numpy as np
    from benchmarks.chip import session
    sess = object.__new__(session.Session)
    sess.state, sess.eta = 0, 0.0
    sess.step = lambda state, sb, eta: (state + 1, SimpleNamespace(
        losses=np.ones(2)))
    marks = []

    def on_drain(so_far):
        if not marks:
            marks.append(time.perf_counter())
            return marks[0] + 0.3
    win = session.Session.window(sess, iter(range(10**9)), 0.01,
                                 on_drain=on_drain)
    end = win["t_start"] + win["seconds"]
    assert end - marks[0] >= 0.3
    assert win["rounds"] == sess.state and win["rounds"] > 5


def test_unknown_names_are_refused():
    with pytest.raises(FileNotFoundError):
        cells.load("no-such-cell")
    with pytest.raises(ValueError):
        cells.load("../configs/resnet18")
    with pytest.raises(FileNotFoundError):
        cells.metric_reader("no_such_metric")


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "resnet18.dynamic", "--seed", "7", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# --------------------------------------------------------------------------
# trace classification and reduction
# --------------------------------------------------------------------------

_ROUND = """\
HloModule round

%fused_conv (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %convolution.3 = f32[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
  ROOT %m = f32[8,8]{1,0} multiply(%convolution.3, %p0)
}

%fused_add (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %a = f32[8,8]{1,0} add(%p0, %p0)
}

ENTRY %main (x: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %x = f32[8,8]{1,0:T(8,128)} parameter(0)
  %fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(%x, %x), kind=kOutput, calls=%fused_conv
  %fusion.2 = f32[8,8]{1,0:T(8,128)} fusion(%fusion.1), kind=kLoop, calls=%fused_add
  %shard_map.7 = (f32[8,8]{1,0}, f32[8,8]{1,0}) custom-call(%x, %x), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{}}
  %all-reduce-start.4 = f32[8,8]{1,0} all-reduce-start(%fusion.2), replica_groups={{0,1}}, to_apply=%fused_add
  %all-reduce-done.4 = f32[8,8]{1,0} all-reduce-done(%all-reduce-start.4)
  ROOT %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%all-reduce-done.4, %fusion.1)
}
"""


def test_classify_by_hlo_text():
    from benchmarks.chip import tracing
    kinds = tracing.classify(_ROUND)
    assert kinds["fusion.1"] == "conv" and kinds["fusion.2"] == "op"
    assert kinds["shard_map.7"] == "prox"
    assert kinds["all-reduce-start.4"] == kinds["all-reduce-done.4"] \
        == "coll"
    assert tracing._instr_name(
        "%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8] %x)") == "fusion.1"


def test_reduction_on_a_hand_made_extract():
    """Every number worked out by hand from eight events."""
    from benchmarks.chip import tracing
    ops = [["op", "while.1", 0, 100],          # holds the next four
           ["conv", "fusion.1", 0, 40],
           ["prox", "shard_map.7", 40, 10],
           ["op", "fusion.2", 50, 30],
           ["coll", "all-reduce.4", 80, 20],
           ["op", "copy.9", 150, 10],          # after a 50 ns gap
           ["conv", "fusion.1", 200, 40]]      # after a 40 ns gap
    ex = {"devices": {"/device:TPU:0": ops}, "async": {},
          "host": [["bench.dispatch", 0, 120], ["bench.drain", 120, 130]],
          "window": [0, 250]}
    assert tracing.busy_intervals(ops) == [(0, 100), (150, 160), (200, 240)]
    assert tracing.busy_ns(ops) == 150
    assert tracing.kind_ns(ops, "conv") == 80
    assert tracing.kind_ns(ops, "prox") == 10
    assert tracing.self_ns(ops)[0] == 0
    gaps = tracing.idle_gaps(ex)
    assert gaps[0] == ["bench.drain", 50e-9] and gaps[1][1] == 40e-9
    assert gaps[2] == ["bench.drain", 10e-9]
    top = dict(tracing.top_ops(ex))
    assert top["conv:fusion"] == 80e-9 and top["op:while"] == 0.0
    clipped = tracing.clip(ex, 30, 210)
    assert tracing.busy_ns(clipped["devices"]["/device:TPU:0"]) == 90


def test_reduction_on_a_recorded_chip_trace():
    """A 70 ms cut of a traced resnet18.dynamic window on a v5e chip,
    around its longest idle gap.  The expected numbers were worked out
    apart from the library: busy time by a sweep over every event's start
    (+1) and end (-1), class times as plain sums over the events."""
    from benchmarks.chip import tracing
    with gzip.open(os.path.join(
            TESTDATA, "resnet18.dynamic.trace_extract.json.gz"), "rt") as f:
        ex = json.load(f)
    ops = ex["devices"]["/device:TPU:0"]
    assert len(ops) == 2815
    start, end = ex["window"]
    assert end - start == 70_000_000
    assert tracing.busy_ns(ops) == 12_500_405
    idle = 100.0 * (1 - tracing.busy_ns(ops) / (end - start))
    assert idle == pytest.approx(82.14227857142858)
    assert tracing.kind_ns(ops, "conv") == 6_658_683
    assert tracing.kind_ns(ops, "prox") == 19_162
    assert tracing.kind_ns(ops, "coll") == 0
    assert tracing.idle_gaps(ex, 1) == [["bench.input_next", 0.043480146]]
    # the sweep, here too
    edges = sorted([(o[2], 1) for o in ops] + [(o[2] + o[3], -1)
                                               for o in ops])
    busy, depth, last = 0, 0, None
    for t, x in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + x, t
    assert busy == tracing.busy_ns(ops)
