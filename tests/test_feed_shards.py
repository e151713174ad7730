"""A host-stacked superbatch put on the round's input shardings over four
devices: worker ``w``'s rows, and only those, land on device ``w``, and a
fused round over it reads the same losses as over the device-stacked
superbatch.  Four host devices need their own process: the device count
is fixed when JAX starts."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_SCRIPT = textwrap.dedent("""
    import dataclasses
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.configs.base import ConsensusSpec, ShapeConfig
    from repro.data.pipeline import batches, superbatches
    from repro.data.synthetic import SyntheticImages
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.train.engine import Engine

    W, B, E = 4, 2, 2
    cfg = get_config("resnet18", smoke=True).replace(
        prune_targets=("channel",))
    cfg = cfg.replace(hsadmm=dataclasses.replace(cfg.hsadmm, local_steps=E))
    mesh = make_host_mesh(devices=jax.devices()[:W])
    eng = Engine(build(cfg), mesh, ShapeConfig("feed", "train", 0, W * B),
                 consensus=ConsensusSpec(levels=(2, 2), compact_from_level=1,
                                         granularity="chip", node_size=2))
    shardings = {{k: v.sharding for k, v in eng.superbatch_struct().items()}}

    class HostImages(SyntheticImages):
        def batch_at(self, step):
            return {{k: np.asarray(v)
                    for k, v in super().batch_at(step).items()}}
    stream = HostImages(cfg.img_size, cfg.n_classes, B, W)
    host = next(superbatches(batches(stream), E))
    dev = next(superbatches(({{k: jnp.asarray(v) for k, v in b.items()}}
                             for b in batches(stream)), E))
    assert all(isinstance(v, np.ndarray) for v in host.values())
    assert all(isinstance(v, jax.Array) for v in dev.values())

    put = jax.device_put(host, shardings)
    workers = list(mesh.devices[:, 0])
    for k, arr in put.items():
        assert len(arr.addressable_shards) == W, k
        for s in arr.addressable_shards:
            w = workers.index(s.device)
            rows = np.asarray(s.data)
            assert rows.shape == (E, 1) + host[k].shape[2:], (k, rows.shape)
            assert rows.tobytes() == host[k][:, w:w + 1].tobytes(), (k, w)

    step = eng.round_step_fn(False)
    losses = []
    for sb in (put, jax.device_put(dev, shardings)):
        state = eng.init_state_fn()(jax.random.PRNGKey(0))
        _, m = step(state, sb, jnp.float32(1e-3))
        losses.append(np.asarray(m.losses))
    assert losses[0].tobytes() == losses[1].tobytes(), losses
    print("ok", losses[0].tolist())
""")


def test_host_stacked_superbatch_lands_by_worker_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _SCRIPT.format(src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1].startswith("ok")
