"""Compile-only checks of the Pallas kernels for a TPU v5e that is
described, not attached (``v5e:2x2`` topology).

Interpret mode on the CPU accepts kernels the chip's compiler (Mosaic)
refuses: blocks that are neither (8, 128)-aligned nor whole dims, lane
gathers, minor-dim reshapes.  Each test compiles one kernel natively at
resnet18's real widths — the (R, C) views of its W=4-stacked leaves —
and asserts the kernel is in the executable.  The topology is described
inside a fixture, never at import, and only this file describes it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_prox_sgd import fused_prox_sgd, fused_prox_sgd_dyn
from repro.kernels.group_norms import group_norms_sq
from repro.kernels.wire import quantize_pack_q4, quantize_rows

# distinct (R, C) views of resnet18's leaves stacked over W=4 workers
RESNET18_VIEWS = [(4, 10), (4, 64), (4, 128), (4, 256), (4, 512),
                  (108, 64), (256, 128), (512, 256), (1024, 512), (2048, 10),
                  (2304, 64), (2304, 128), (4608, 128), (4608, 256),
                  (9216, 256), (9216, 512), (18432, 512)]
# R = 300 is no multiple of 8: a padded final row block
UNALIGNED = (300, 576)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be cached but never read back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in txt
    return txt


def test_views_cover_resnet18():
    from repro.configs import get_config
    from repro.core.hsadmm import flatten
    from repro.kernels.ops import _rc
    from repro.models import build
    p = jax.eval_shape(build(get_config("resnet18")).init,
                       jax.random.PRNGKey(0))
    views = {_rc((4,) + tuple(v.shape)) for v in flatten(p).values()}
    assert views == set(RESNET18_VIEWS)


@pytest.mark.parametrize("R,C", RESNET18_VIEWS + [UNALIGNED])
def test_fused_prox_sgd_dyn_compiles(one_chip, R, C):
    f32 = jnp.float32
    _hlo(lambda *a: fused_prox_sgd_dyn(*a, momentum=0.9),
         *[((R, C), f32)] * 5, ((R, 1), f32), ((1, 1), f32),
         sharding=one_chip)


def test_fused_prox_sgd_static_compiles(one_chip):
    _hlo(lambda *a: fused_prox_sgd(*a, eta=1e-2, rho=1e-3, momentum=0.9),
         *[(UNALIGNED, jnp.float32)] * 5, sharding=one_chip)


# (2048, 32000): an LM-head-wide row; the row block shrinks to fit VMEM
@pytest.mark.parametrize("R,C", [(18432, 512), (2048, 10), (4, 512),
                                 UNALIGNED, (2048, 32000)])
def test_quantize_rows_compiles(one_chip, R, C):
    _hlo(quantize_rows, ((R, C), jnp.float32), sharding=one_chip)


@pytest.mark.parametrize("R,C", [(18432, 512), (2048, 10), (4, 512),
                                 UNALIGNED, (8, 301), (2048, 32000)])
def test_quantize_pack_q4_compiles(one_chip, R, C):
    _hlo(quantize_pack_q4, ((R, C), jnp.float32), sharding=one_chip)


@pytest.mark.parametrize("G,C,K", [(1, 512, 4608), (1, 64, 27),
                                   (4, 300, 1100)])
def test_group_norms_compiles(one_chip, G, C, K):
    _hlo(group_norms_sq, ((G, C, K), jnp.float32), sharding=one_chip)


def test_prox_update_runs_per_worker_shard(topo, monkeypatch):
    """Over W=4 workers on data=4 the prox kernel sees one worker's leaf
    per chip (view 576 x 64 of a (4, 3, 3, 64, 64) conv stack), never the
    all-gathered stack (2304 x 64)."""
    from repro.configs.base import ConsensusSpec, HsadmmConfig
    from repro.core.hsadmm import EngineSpec, _prox_update
    from repro.core.sparsity import SparsityPlan
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    # the shim picks the native kernel from the default backend, which is
    # the CPU here; the described chips need it
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = make_host_mesh(devices=topo.devices)
    spec = EngineSpec(plan=SparsityPlan(()),
                      consensus=ConsensusSpec(levels=(2, 2)),
                      hp=HsadmmConfig(), worker_mesh=mesh)
    stack = NamedSharding(mesh, P("data"))
    shape = (4, 3, 3, 64, 64)
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=stack)] * 5
    args += [jax.ShapeDtypeStruct((1, 1, 1, 1, 1), jnp.float32,
                                  sharding=NamedSharding(mesh, P())),
             jax.ShapeDtypeStruct((), jnp.float32,
                                  sharding=NamedSharding(mesh, P()))]
    txt = jax.jit(lambda *a: _prox_update(spec, *a)).lower(*args) \
        .compile().as_text()
    calls = [l for l in txt.splitlines() if "tpu_custom_call" in l]
    assert calls and all("f32[576,64]" in l for l in calls)
    assert not any("f32[2304,64]" in l for l in calls)
    assert " all-gather(" not in txt
