"""Per-kernel interpret-mode validation vs ref.py oracles, with
shape/dtype sweeps (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, wire
from repro.kernels.fused_prox_sgd import fused_prox_sgd_dyn


@pytest.mark.parametrize("shape", [(4, 128), (6, 128, 256), (2, 3, 64, 384),
                                   (128,), (7,), ()])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_prox_sgd(shape, dtype):
    # (128,)/(7,)/() regression: 1-D bias vectors and 0-D scalars must pad
    # to one (1, N) row instead of crashing the 2D reshape
    k = jax.random.PRNGKey(0)
    xs = [jax.random.normal(jax.random.fold_in(k, i), shape).astype(dtype)
          for i in range(5)]
    t, m = ops.fused_prox_sgd(*xs, eta=1e-2, rho=1e-3, momentum=0.9)
    assert t.shape == shape and m.shape == shape
    tr, mr = ref.fused_prox_sgd_ref(*xs, eta=1e-2, rho=1e-3, momentum=0.9)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(t, np.float32),
                               np.asarray(tr, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(m, np.float32),
                               np.asarray(mr, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("shape,rshape", [
    ((4, 3, 8, 16), (1, 3, 1, 1)),    # layer-wise adaptive rho
    ((4, 16), (1, 1)),                # bias-like leaf
    ((4,), (1,)),                     # 1-D leaf (one padded row)
    ((4, 3, 8, 16), (1, 3, 1, 16)),   # rho varies on minor axis -> fallback
    ((8,), (8,)),                     # 1-D leaf, per-element rho -> fallback
])
def test_prox_sgd_update_shim(shape, rshape):
    """The hot-path dispatch shim: traced eta + array rho (the adaptive
    penalties change every round) must match the inline jnp update."""
    k = jax.random.PRNGKey(0)
    xs = [jax.random.normal(jax.random.fold_in(k, i), shape)
          for i in range(5)]
    rho = jax.random.uniform(jax.random.fold_in(k, 9), rshape) + 0.1
    eta = jnp.float32(3e-3)
    t, m = jax.jit(lambda *a: ops.prox_sgd_update(*a, momentum=0.9))(
        *xs, rho, eta)
    gtot = xs[1] + rho * (xs[0] - xs[2] + xs[3])
    mr = 0.9 * xs[4] + gtot
    tr = xs[0] - eta * mr
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                               rtol=1e-6, atol=1e-6)


def test_prox_sgd_update_fallbacks():
    k = jax.random.PRNGKey(1)
    th, g = (jax.random.normal(jax.random.fold_in(k, i), (4, 8))
             for i in (0, 1))
    eta = jnp.float32(1e-2)
    # solo (no consensus operands): plain SGD
    t, m = ops.prox_sgd_update(th, g, None, None, None, None, eta)
    assert m is None
    np.testing.assert_allclose(np.asarray(t), np.asarray(th - 1e-2 * g),
                               rtol=1e-6)
    # momentum-free prox step
    z, u = th * 0.5, th * 0.1
    t, m = ops.prox_sgd_update(th, g, z, u, None, jnp.float32(0.3), eta)
    assert m is None
    np.testing.assert_allclose(
        np.asarray(t), np.asarray(th - 1e-2 * (g + 0.3 * (th - z + u))),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("R,C", [(13, 300), (300, 576), (8, 128), (1, 7)])
def test_fused_prox_sgd_dyn_padded_grid(R, C):
    """Blocks are (8, 128)-aligned or whole dims (the TPU tiling rule);
    a dim they do not divide gets a padded final block whose pad never
    reaches the outputs."""
    k = jax.random.PRNGKey(4)
    xs = [jax.random.normal(jax.random.fold_in(k, i), (R, C))
          for i in range(5)]
    rho = jax.random.uniform(jax.random.fold_in(k, 9), (R, 1)) + 0.1
    eta = jnp.full((1, 1), 3e-3, jnp.float32)
    t, m = fused_prox_sgd_dyn(*xs, rho, eta, momentum=0.9, block_r=8,
                              block_c=128, interpret=True)
    mr = 0.9 * xs[4] + xs[1] + rho * (xs[0] - xs[2] + xs[3])
    tr = xs[0] - 3e-3 * mr
    np.testing.assert_allclose(np.asarray(m), np.asarray(mr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("C,B", [(64, 24), (128, 64), (32, 8)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_compact_expand(C, B, dtype):
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (4, C, 32)).astype(dtype)
    idx = jnp.sort(jax.random.permutation(k, C)[:B]).astype(jnp.int32)
    c = ops.compact_groups(x, idx)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(x[:, idx, :]))
    e = ops.expand_groups(c, idx, full=C)
    mask = jnp.zeros((C,)).at[idx].set(1.0)
    ref_e = (x.astype(jnp.float32) * mask[None, :, None]).astype(dtype)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(ref_e))


@pytest.mark.parametrize("G,C,K", [(5, 128, 384), (1, 64, 1024), (8, 16, 48),
                                   (2, 300, 1100)])
def test_group_norms(G, C, K):
    x = jax.random.normal(jax.random.PRNGKey(2), (G, C, K))
    np.testing.assert_allclose(np.asarray(ops.group_norms_sq(x)),
                               np.asarray(ref.group_norms_ref(x)),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# wire-path kernels (kernels/wire.py) vs ref.py oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("R,C", [(7, 13), (4, 128), (257, 6), (1, 1)])
def test_quantize_rows_vs_ref(R, C):
    x = jax.random.normal(jax.random.PRNGKey(1), (R, C)) * 3.0
    q, s = wire.quantize_rows(x, block_r=8, interpret=True)
    qr, sr = ref.quantize_rows_ref(x)
    assert q.dtype == jnp.int8 and s.shape == (R, 1)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


@pytest.mark.parametrize("R,C", [(7, 13), (4, 16), (5, 1), (257, 7),
                                 (9, 600), (3, 512), (2, 257)])
def test_quantize_pack_q4_vs_ref(R, C):
    """Odd minor dims exercise the zero pad nibble; C > 256 runs the
    looped 256-lane MXU pack steps, with and without a tail step."""
    x = jax.random.normal(jax.random.PRNGKey(4), (R, C))
    p, s = wire.quantize_pack_q4(x, block_r=8, interpret=True)
    prr, srr = ref.quantize_pack_q4_ref(x)
    assert p.dtype == jnp.uint8 and p.shape == (R, (C + 1) // 2)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(prr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(srr), rtol=1e-6)


@pytest.mark.parametrize("shape", [(2, 3, 17), (9,), ()])
def test_wire_ops_rank_edges(shape):
    """The any-rank ops shims: 1-D leaves pad to one (1, N) row and 0-D
    scalars to (1, 1) instead of crashing the 2-D reshape; decode∘encode
    stays within the per-row quantization bound."""
    x = jax.random.normal(jax.random.PRNGKey(7), shape) * 2.0
    q, s = ops.quantize_rows(x)
    assert q.shape == shape
    y = ops.dequantize_rows(q, s)
    assert y.shape == shape
    bound = (np.abs(np.asarray(x)).max() if x.size else 0.0) / 127 + 1e-6
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=bound)
    p, s4 = ops.quantize_pack_q4(x)
    n = shape[-1] if shape else 1
    assert p.shape == (shape[:-1] if shape else ()) + ((n + 1) // 2,)
    y4 = ops.unpack_dequantize_q4(p, s4, n)
    # shim output is (..., n); codecs reshape 0-D via the dense template
    assert y4.shape == (shape if shape else (1,))
    bound4 = (np.abs(np.asarray(x)).max() if x.size else 0.0) / 7 + 1e-6
    np.testing.assert_allclose(np.asarray(y4).reshape(shape),
                               np.asarray(x), atol=bound4)


@pytest.mark.parametrize("codec_bits", [8, 4])
def test_scatter_dequantize_zero_fill(codec_bits):
    """compact wire roundtrip through the ops shims: kept channels match
    within quantization error, dropped channels come back exactly zero."""
    k = jax.random.PRNGKey(8)
    C, B = 23, 11
    x = jax.random.normal(k, (7, C))
    idx = jnp.sort(jax.random.permutation(k, C)[:B]).astype(jnp.int32)
    if codec_bits == 8:
        q, s = ops.gather_quantize(x, idx)
        out = ops.scatter_dequantize(q, s, idx, C)
        bound = float(np.abs(np.asarray(x[:, idx])).max()) / 127 + 1e-6
    else:
        p, s = ops.gather_quantize_q4(x, idx)
        out = ops.scatter_dequantize_q4(p, s, idx, C)
        bound = float(np.abs(np.asarray(x[:, idx])).max()) / 7 + 1e-6
    mask = np.zeros(C); mask[np.asarray(idx)] = 1
    np.testing.assert_allclose(np.asarray(out)[:, mask == 1],
                               np.asarray(x[:, idx]), atol=bound)
    assert np.all(np.asarray(out)[:, mask == 0] == 0.0)
