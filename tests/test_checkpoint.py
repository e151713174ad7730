"""Checkpoint/restart + elastic worker-count changes (DESIGN.md §6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import checkpoint as ckpt


def _state(W):
    return {
        "theta": {"w": jnp.arange(W * 6, dtype=jnp.float32).reshape(W, 6)},
        "mom": {"w": jnp.ones((W, 6))},
        "u": {"w": jnp.full((W, 6), 2.0)},
        "z": [{"w": jnp.full((W // 2, 6), 3.0)}, {"w": jnp.full((1, 6), 4.0)}],
        "v": [{"w": jnp.zeros((W // 2, 6))}],
        "k": jnp.asarray(7, jnp.int32),
        "weights": jnp.ones((W,)),
    }


def test_save_restore_roundtrip(tmp_path):
    st = _state(4)
    ckpt.save(str(tmp_path), st, {"step": 7})
    last = ckpt.latest(str(tmp_path))
    tmpl = jax.tree.map(jnp.zeros_like, st)
    st2, meta = ckpt.restore(last, tmpl)
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_failed_background_save_raises_from_flush(tmp_path):
    """A background save that fails must fail the run at flush(), not
    print and carry on; the writer stays alive for the saves after it."""
    st = _state(4)
    ckpt.save(str(tmp_path), st, {"step": 1, "bad": object()},
              background=True)                  # meta.json cannot encode
    ckpt.save(str(tmp_path), st, {"step": 2}, background=True)
    with pytest.raises(TypeError):
        ckpt.flush()
    assert ckpt.latest(str(tmp_path)).endswith("ckpt_00000002")
    assert sorted(p.name for p in tmp_path.iterdir()) == ["ckpt_00000002"]
    ckpt.flush()                                # reported once


def test_keep_policy(tmp_path):
    st = _state(4)
    for s in range(5):
        ckpt.save(str(tmp_path), st, {"step": s}, keep=2)
    import os
    assert len([d for d in os.listdir(tmp_path)
                if d.startswith("ckpt_")]) == 2


def test_elastic_scale_up_seeds_new_workers_from_z(tmp_path):
    st = _state(4)
    ckpt.save(str(tmp_path), st, {"step": 1})
    tmpl = jax.tree.map(jnp.zeros_like, _state(8))
    st2, _ = ckpt.restore_elastic(ckpt.latest(str(tmp_path)), tmpl, 8)
    # surviving workers keep their theta
    np.testing.assert_array_equal(np.asarray(st2["theta"]["w"][:4]),
                                  np.asarray(st["theta"]["w"]))
    # new workers seeded from global z (=4.0), duals zero
    assert np.all(np.asarray(st2["theta"]["w"][4:]) == 4.0)
    assert np.all(np.asarray(st2["u"]["w"][4:]) == 0.0)


def test_elastic_scale_down(tmp_path):
    st = _state(8)
    ckpt.save(str(tmp_path), st, {"step": 1})
    tmpl = jax.tree.map(jnp.zeros_like, _state(4))
    st2, _ = ckpt.restore_elastic(ckpt.latest(str(tmp_path)), tmpl, 4)
    np.testing.assert_array_equal(np.asarray(st2["theta"]["w"]),
                                  np.asarray(st["theta"]["w"][:4]))
