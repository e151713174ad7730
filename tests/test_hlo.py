"""dist.hlo / dist.hlo_cost: collective parsing, axis/fabric
classification, byte grouping, and trip-count weighting.

Unit tests run on a synthetic-but-faithful HLO module (formats taken
verbatim from XLA:CPU output); one integration test compiles a real
jitted all-reduce in a subprocess (the forced multi-device host platform
must be configured before jax initializes, which pytest already did)."""
import json
import os
import subprocess
import sys

import numpy as np

from repro.dist import hlo
from repro.dist.hlo_cost import multiplicities, weighted_cost

MODULE = """\
HloModule jit_f, entry_computation_layout={(f32[2,8]{1,0})->f32[2,4]{1,0}}

%add.clone (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %add.2 = f32[] add(f32[] %x.1, f32[] %y.1)
}

%region_1.16_spmd (param.2: (s32[], f32[2,4])) -> (s32[], f32[2,4]) {
  %param.2 = (s32[], f32[2,4]{1,0}) parameter(0)
  %gte.1 = f32[2,4]{1,0} get-tuple-element((s32[], f32[2,4]{1,0}) %param.2), index=1
  %c.1 = f32[4,4]{1,0} constant({...})
  %dot.1 = f32[2,4]{1,0} dot(f32[2,4]{1,0} %gte.1, f32[4,4]{1,0} %c.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-reduce = f32[2,4]{1,0} all-reduce(f32[2,4]{1,0} %dot.1), channel_id=3, replica_groups={{0,2,4,6},{1,3,5,7}}, use_global_device_ids=true, to_apply=%add.clone
  %gte.0 = s32[] get-tuple-element((s32[], f32[2,4]{1,0}) %param.2), index=0
  %one.1 = s32[] constant(1)
  %add.3 = s32[] add(s32[] %gte.0, s32[] %one.1)
  ROOT %tuple.5 = (s32[], f32[2,4]{1,0}) tuple(s32[] %add.3, f32[2,4]{1,0} %all-reduce)
}

%region_2.24_spmd (param.3: (s32[], f32[2,4])) -> pred[] {
  %param.3 = (s32[], f32[2,4]{1,0}) parameter(0)
  %gte.2 = s32[] get-tuple-element((s32[], f32[2,4]{1,0}) %param.3), index=0
  %five.1 = s32[] constant(5)
  ROOT %lt.1 = pred[] compare(s32[] %gte.2, s32[] %five.1), direction=LT
}

ENTRY %main.35_spmd (param.1: f32[2,8]) -> f32[2,4] {
  %param.1 = f32[2,8]{1,0} parameter(0)
  %slice.1 = f32[2,4]{1,0} slice(f32[2,8]{1,0} %param.1), slice={[0:2], [0:4]}
  %all-reduce.1 = f32[2,4]{1,0} all-reduce(f32[2,4]{1,0} %slice.1), channel_id=1, replica_groups=[2,4]<=[4,2]T(1,0), use_global_device_ids=true, to_apply=%add.clone
  %permute.1 = f32[2,4]{1,0} collective-permute(f32[2,4]{1,0} %all-reduce.1), channel_id=2, source_target_pairs={{0,4},{4,0},{1,5},{5,1}}
  %zero.1 = s32[] constant(0)
  %tuple.3 = (s32[], f32[2,4]{1,0}) tuple(s32[] %zero.1, f32[2,4]{1,0} %permute.1)
  %while = (s32[], f32[2,4]{1,0}) while((s32[], f32[2,4]{1,0}) %tuple.3), condition=%region_2.24_spmd, body=%region_1.16_spmd, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %gte.9 = f32[2,4]{1,0} get-tuple-element((s32[], f32[2,4]{1,0}) %while), index=1
}
"""


def test_collective_parsing_literal_and_iota_groups():
    colls = hlo.collective_stats(MODULE, model=2, data=4, node=2)
    kinds = sorted(c.kind for c in colls)
    assert kinds == ["all-reduce", "all-reduce", "collective-permute"]
    by_comp = {c.computation: c for c in colls if c.kind == "all-reduce"}
    body = by_comp["region_1.16_spmd"]
    entry = by_comp["main.35_spmd"]
    # payload: f32[2,4] = 32 bytes; both encodings give 2 groups of 4
    for c in (body, entry):
        assert c.payload_bytes == 32
        assert c.group_size == 4 and c.n_groups == 2
    # iota [2,4]<=[4,2]T(1,0) expands to {{0,2,4,6},{1,3,5,7}}
    assert entry.replica_groups == [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_axis_and_fabric_classification():
    # stride-2 groups on a (data=4, model=2) mesh walk the data axis;
    # node_size decides intra vs inter node
    colls = hlo.collective_stats(MODULE, model=2, data=4, node=2)
    ar = [c for c in colls if c.kind == "all-reduce"][0]
    assert (ar.axis, ar.fabric) == ("data", "inter_node")
    colls4 = hlo.collective_stats(MODULE, model=2, data=4, node=4)
    ar4 = [c for c in colls4 if c.kind == "all-reduce"][0]
    assert (ar4.axis, ar4.fabric) == ("data", "intra_node")
    # the permute jumps stride 4 = model*data/2... here 4 >= model*data/pod
    perm = [c for c in colls if c.kind == "collective-permute"][0]
    assert perm.axis == "data" and perm.fabric == "inter_node"


def test_axis_bytes_groups_by_fabric():
    colls = hlo.collective_stats(MODULE, model=2, data=4, node=2)
    ab = hlo.axis_bytes(colls)
    # two ring all-reduces: 2*(3/4)*32 = 48 each; permute: 32
    assert ab == {"inter_node": 48.0 * 2 + 32.0}
    assert hlo.internode_bytes(colls) == 128.0
    s = hlo.summarize(colls)
    assert s["total_count"] == 3
    assert s["by_kind"]["all-reduce"]["count"] == 2


def test_weighted_cost_applies_trip_counts():
    comps, entry = hlo.parse_computations(MODULE)
    assert entry == "main.35_spmd"
    mult = multiplicities(comps, entry)
    assert mult["main.35_spmd"] == 1
    assert mult["region_1.16_spmd"] == 5      # while body, 5 trips
    assert mult["region_2.24_spmd"] == 5
    assert mult["add.clone"] >= 5             # called from both all-reduces

    wc = weighted_cost(MODULE, model=2, data=4, node=2)
    # only the body has a dot: 2 * prod(2,4) * contracted(4) = 64/trip
    assert wc.flops == 5 * 64.0
    trips = {(c.computation, c.kind): c.trips for c in wc.collectives}
    assert trips[("region_1.16_spmd", "all-reduce")] == 5
    assert trips[("main.35_spmd", "all-reduce")] == 1
    s = hlo.summarize(wc.collectives)
    assert s["by_kind"]["all-reduce"]["count"] == 6   # 5 in-loop + 1 entry


def test_shape_bytes_tuples_and_dtypes():
    assert hlo.shape_bytes("f32[2,4]{1,0}") == 32
    assert hlo.shape_bytes("(s32[], f32[2,4]{1,0})") == 4 + 32
    assert hlo.shape_bytes("bf16[8]") == 16
    assert hlo.shape_bytes("pred[]") == 1


_SUBPROC = """\
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.dist import hlo

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model=2)
x = jax.device_put(jnp.arange(32.0).reshape(4, 8),
                   NamedSharding(mesh, P("data", "model")))
f = jax.jit(lambda x: x.reshape(2, 2, 8).sum(0),
            out_shardings=NamedSharding(mesh, P(None, "model")))
txt = f.lower(x).compile().as_text()
colls = hlo.collective_stats(txt, model=2, data=2, node=1)
print(json.dumps([[c.kind, c.payload_bytes, c.group_size, c.axis, c.fabric]
                  for c in colls]))
"""


def test_real_jitted_all_reduce_parses():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    colls = json.loads(r.stdout.strip().splitlines()[-1])
    ars = [c for c in colls if c[0] == "all-reduce"]
    assert len(ars) == 1
    kind, payload, gsize, axis, fabric = ars[0]
    # per-device shard after the reduce is f32[2,4] = 32 bytes, reduced
    # over the 2-wide data axis (node=1 -> inter-node fabric)
    assert payload == 32 and gsize == 2
    assert (axis, fabric) == ("data", "inter_node")
