"""The four-chip cell ``resnet18.dp4.dynamic`` runs the harness's whole
traced run at smoke size over four CPU devices (one worker on each, the
top exchange crossing devices) and reads correct.  Four host devices
need their own process: the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
import textwrap

from bench_smoke import ROOT

_SCRIPT = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {tests!r}, {src!r}]
    import jax, pytest
    from bench_smoke import smoke_cell
    from benchmarks.chip import peaks, run
    mp = pytest.MonkeyPatch()
    cell, _ = smoke_cell("resnet18.dp4.dynamic", mp)
    mp.setattr(peaks, "peaks", lambda kind: {{
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}})
    res = run.run_cell(cell, 2**31 + 4004, 2.5, True, jax.devices(),
                       time.perf_counter())
    print(json.dumps(res))
""")


def test_four_device_cell_runs_at_smoke_size():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _SCRIPT.format(root=ROOT, tests=os.path.dirname(__file__),
                          src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert res["metrics"]["feed.produce_ms_per_round"]["value"] > 0
    assert "[feed]" in p.stderr
