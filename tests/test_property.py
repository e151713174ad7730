"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.comm import get_codec
from repro.core.sparsity import (GroupRule, LeafAxis, SparsityPlan,
                                 topk_mask, project)
from repro.core.shrinkage import compact_leaf, expand_leaf
from repro.core.masks import MaskSyncConfig, sync_masks

SETTINGS = dict(max_examples=25, deadline=None)


@given(C=st.integers(4, 64), frac=st.floats(0.1, 1.0),
       seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_topk_mask_counts_and_membership(C, frac, seed):
    keep = max(1, int(C * frac))
    s = jax.random.uniform(jax.random.PRNGKey(seed), (2, C))
    mask, idx = topk_mask(s, keep)
    assert np.all(np.asarray(mask.sum(-1)) == keep)
    # mask positions == idx set
    for r in range(2):
        assert set(np.flatnonzero(np.asarray(mask[r]))) == \
            set(np.asarray(idx[r]).tolist())


@given(C=st.sampled_from([16, 32, 64]), shards=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_compact_expand_equals_mask(C, shards, seed):
    """expand(compact(x)) == x * mask — the §4.4 pipeline is lossless on
    the kept support and exactly zero elsewhere."""
    keep = C // 2
    key = jax.random.PRNGKey(seed)
    s = jax.random.uniform(key, (C,))
    mask, idx = topk_mask(s, keep, shards)
    x = jax.random.normal(key, (3, C, 4))
    c = compact_leaf(x, idx, ax=1, stack_ndims=0, offset=1, shards=shards)
    e = expand_leaf(c, idx, ax=1, full=C, stack_ndims=0, offset=1,
                    shards=shards)
    np.testing.assert_allclose(np.asarray(e),
                               np.asarray(x * mask[None, :, None]),
                               rtol=1e-6)


@given(seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_projection_norm_nonincreasing(seed):
    key = jax.random.PRNGKey(seed)
    p = {"w": jax.random.normal(key, (8, 16))}
    plan = SparsityPlan((GroupRule("g", (LeafAxis("w", 1),), groups=16,
                                   keep=8, stack_ndims=0),))
    proj, _ = project(p, plan)
    assert float(jnp.sum(proj["w"]**2)) <= float(jnp.sum(p["w"]**2)) + 1e-6


@given(M=st.integers(2, 6), seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_bitwise_or_union_superset(M, seed):
    """Eq. 14: the global mask contains every node's local support
    (given enough static budget)."""
    C, keep = 16, 4
    rule = GroupRule("g", (LeafAxis("w", 1),), groups=C, keep=keep,
                     stack_ndims=0)
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (M, C))
    cfg = MaskSyncConfig("bitwise_or", slack=float(M))
    idx, valid, mask = sync_masks(scores, rule, cfg)
    union = np.zeros(C)
    for i in range(M):
        _, li = topk_mask(scores[i], keep)
        union[np.asarray(li)] = 1
    assert np.all(np.asarray(mask) >= union)


# ---------------------------------------------------------------------------
# wire codecs (repro.comm)
# ---------------------------------------------------------------------------


@given(lead=st.sampled_from([2, 4]), n=st.integers(3, 40),
       seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_dense_codec_group_reduce_exact(lead, n, seed):
    """The dense codec is an exact weighted group-sum (bit-for-bit the
    reference reduction)."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (lead, n))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (lead,)) + 0.1
    red, _ = get_codec("dense").group_reduce({"x": x}, lead, w)
    ref = (x * w[:, None]).reshape(1, lead, n).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(red["x"]), np.asarray(ref))


@given(lead=st.sampled_from([2, 4]), n=st.integers(3, 40),
       scale=st.floats(1e-3, 1e3), seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_q8_codec_error_bounded_per_leaf(lead, n, scale, seed):
    """q8 group-sum error <= sum over members of max|x_m|/127 per leaf
    (per-member symmetric-quantization bound, any magnitude scale)."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (lead, n)) * scale
    w = jnp.ones((lead,))
    dense, _ = get_codec("dense").group_reduce({"x": x}, lead, w)
    q8, _ = get_codec("q8").group_reduce({"x": x}, lead, w)
    bound = float(np.abs(np.asarray(x)).max(-1).sum()) / 127.0 + 1e-6
    assert float(jnp.max(jnp.abs(q8["x"] - dense["x"]))) <= bound


@given(rate=st.floats(0.05, 0.9), rounds=st.integers(2, 6),
       seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_topk_codec_error_feedback_sums_to_dense(rate, rounds, seed):
    """Over any number of rounds, accumulated top-k reductions + the
    pending residual == the accumulated dense reduction (DGC error
    feedback is lossless bookkeeping)."""
    codec = get_codec(f"topk:{rate}")
    lead = 4
    key = jax.random.PRNGKey(seed)
    st_ef, acc, dense_acc = None, 0.0, 0.0
    w = jnp.ones((lead,))
    for r in range(rounds):
        x = jax.random.normal(jax.random.fold_in(key, r), (lead, 24))
        red, st_ef = codec.group_reduce({"x": x}, lead, w, st_ef)
        acc = acc + red["x"]
        dense_acc = dense_acc + x.sum(0, keepdims=True)
    total = acc + st_ef["x"].sum(0, keepdims=True)
    np.testing.assert_allclose(np.asarray(total), np.asarray(dense_acc),
                               rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_score_consensus_masks_identical_across_nodes(seed):
    rule = GroupRule("g", (LeafAxis("w", 1),), groups=32, keep=16,
                     stack_ndims=0)
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (4, 32))
    idx, valid, mask = sync_masks(scores, rule,
                                  MaskSyncConfig("score_consensus"))
    assert mask.shape == (32,)          # one global mask, no node dim
    assert float(mask.sum()) == 16


@given(R=st.integers(1, 9), C=st.integers(1, 33), seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_q4_pack_unpack_roundtrip_bit_exact(R, C, seed):
    """Nibble packing is lossless: unpack(pack(q)) == q for every 4-bit
    value, any (odd or even) minor dim."""
    from repro.kernels import ref
    q = jax.random.randint(jax.random.PRNGKey(seed), (R, C), -7, 8)
    p = ref.pack_q4_ref(q)
    assert p.shape == (R, (C + 1) // 2) and p.dtype == jnp.uint8
    back = ref.unpack_q4_ref(p, C)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


@given(R=st.integers(1, 7), C=st.integers(1, 40),
       scale=st.floats(1e-3, 1e3), seed=st.integers(0, 2**16))
@settings(**SETTINGS)
def test_fused_q8_encode_matches_stock(R, C, scale, seed):
    """The one-pass Pallas encode produces bit-identical int8 payloads
    and scales to the stock two-pass reference at any magnitude.  Both
    sides are compiled programs: XLA folds the division by the constant
    ``levels`` into a reciprocal multiply under jit, one ulp away from
    the op-by-op division at some magnitudes."""
    from repro.kernels import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(seed), (R, C)) * scale
    q, s = ops.quantize_rows(x)
    qr, sr = jax.jit(ref.quantize_rows_ref)(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr.reshape(R, 1)),
                               rtol=1e-7)


@given(C=st.integers(4, 32), seed=st.integers(0, 2**16),
       bits=st.sampled_from([8, 4]))
@settings(**SETTINGS)
def test_fused_decode_encode_idempotent_on_kept(C, seed, bits):
    """decode∘encode is idempotent on the kept channels: re-encoding an
    already-quantized buffer reproduces the identical payload (the wire
    grid is a fixed point), and dropped channels stay exactly zero."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(seed)
    B = max(1, C // 2)
    x = jax.random.normal(key, (3, C))
    idx = jnp.sort(jax.random.permutation(key, C)[:B]).astype(jnp.int32)
    if bits == 8:
        enc = lambda v: ops.gather_quantize(v, idx)
        dec = lambda pl: ops.scatter_dequantize(*pl, idx, C)
    else:
        enc = lambda v: ops.gather_quantize_q4(v, idx)
        dec = lambda pl: ops.scatter_dequantize_q4(*pl, idx, C)
    y = dec(enc(x))
    y2 = dec(enc(y))
    np.testing.assert_array_equal(np.asarray(enc(y)[0]),
                                  np.asarray(enc(x)[0]))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y),
                               rtol=2e-6, atol=0)
    mask = np.zeros(C); mask[np.asarray(idx)] = 1
    assert np.all(np.asarray(y)[:, mask == 0] == 0.0)
