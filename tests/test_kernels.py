"""Per-kernel validation: shape/dtype sweeps vs. the pure-jnp oracles.

Kernels run in interpret mode on this CPU container (the TPU-target
BlockSpecs are exercised structurally either way).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    decode_attention,
    decode_attention_ref,
    masked_l2_topk,
    masked_l2_topk_ref,
)


def _rand(rng, shape, dtype=np.float32):
    return rng.normal(0, 1, shape).astype(dtype)


# ----------------------------------------------------------------------
# masked_l2 kernel
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,n,d", [(4, 600, 32), (128, 512, 128), (130, 1500, 200), (1, 512, 64)])
@pytest.mark.parametrize("k", [1, 10])
def test_masked_l2_shapes(b, n, d, k):
    rng = np.random.default_rng(b * 1000 + n + d + k)
    q = _rand(rng, (b, d))
    x = _rand(rng, (n, d))
    mask = rng.random(n) < 0.5
    d_k, i_k = masked_l2_topk(q, x, jnp.asarray(mask), k, interpret=True)
    d_r, i_r = masked_l2_topk_ref(jnp.asarray(q), jnp.asarray(x), jnp.asarray(mask), k)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=2e-4, atol=2e-4)
    # indices may differ on exact distance ties; compare via distances
    assert (np.asarray(i_k) >= -1).all()
    match = (np.asarray(i_k) == np.asarray(i_r)).mean()
    assert match > 0.95, f"index agreement {match}"


def test_masked_l2_all_masked_out():
    rng = np.random.default_rng(0)
    q = _rand(rng, (8, 64))
    x = _rand(rng, (700, 64))
    mask = np.zeros(700, bool)
    d_k, i_k = masked_l2_topk(q, x, jnp.asarray(mask), 5, interpret=True)
    assert (np.asarray(i_k) == -1).all()


def test_masked_l2_selective_mask_semantics():
    rng = np.random.default_rng(1)
    q = _rand(rng, (4, 32))
    x = _rand(rng, (1024, 32))
    mask = np.zeros(1024, bool)
    mask[100:200] = True
    _, i_k = masked_l2_topk(q, x, jnp.asarray(mask), 8, interpret=True)
    i_k = np.asarray(i_k)
    assert (((i_k >= 100) & (i_k < 200)) | (i_k == -1)).all()


def test_masked_l2_padding_never_returned():
    """Corpus padded to TN multiples — padding rows must never appear."""
    rng = np.random.default_rng(2)
    q = _rand(rng, (4, 48))
    x = _rand(rng, (513, 48))  # forces 1023-row pad
    mask = np.ones(513, bool)
    _, i_k = masked_l2_topk(q, x, jnp.asarray(mask), 10, interpret=True)
    assert (np.asarray(i_k) < 513).all()


@pytest.mark.parametrize("k", [1, 10, 100])
def test_masked_l2_ties_match_ref_ids(k):
    """Duplicated corpus rows tie exactly: the kernel's k-round selection
    must return the reference's ids (ascending distance, lower id first),
    across query tiles and corpus tiles."""
    rng = np.random.default_rng(40 + k)
    base = _rand(rng, (40, 64))
    x = base[rng.integers(0, 40, 1500)]
    q = _rand(rng, (40, 64))
    mask = rng.random(1500) < 0.6
    d_k, i_k = masked_l2_topk(q, x, jnp.asarray(mask), k, interpret=True)
    d_r, i_r = masked_l2_topk_ref(jnp.asarray(q), jnp.asarray(x), jnp.asarray(mask), k)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-5, atol=1e-4)


def test_masked_l2_fewer_passing_than_k():
    """k larger than the passing set: the tail is -1, exactly as the ref."""
    rng = np.random.default_rng(5)
    q = _rand(rng, (8, 32))
    x = _rand(rng, (1100, 32))
    mask = np.zeros(1100, bool)
    mask[rng.choice(1100, 30, replace=False)] = True
    _, i_k = masked_l2_topk(q, x, jnp.asarray(mask), 100, interpret=True)
    _, i_r = masked_l2_topk_ref(jnp.asarray(q), jnp.asarray(x), jnp.asarray(mask), 100)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    assert (np.asarray(i_k)[:, 30:] == -1).all()


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_masked_l2_dtypes(dtype):
    rng = np.random.default_rng(3)
    q = _rand(rng, (8, 64), dtype)
    x = _rand(rng, (600, 64), dtype)
    mask = np.ones(600, bool)
    d_k, _ = masked_l2_topk(q, x, jnp.asarray(mask), 4, interpret=True)
    d_r, _ = masked_l2_topk_ref(
        jnp.asarray(q, jnp.float32), jnp.asarray(x, jnp.float32), jnp.asarray(mask), 4
    )
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=2e-2, atol=2e-2)


# ----------------------------------------------------------------------
# decode_attention kernel
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,kv,gq,s,dh",
    [(2, 4, 2, 1024, 64), (1, 2, 8, 512, 128), (3, 1, 4, 1536, 64), (2, 8, 1, 512, 128)],
)
def test_decode_attention_shapes(b, kv, gq, s, dh):
    rng = np.random.default_rng(b + kv + gq + s)
    q = _rand(rng, (b, kv, gq, dh)) * 0.1
    k = _rand(rng, (b, kv, s, dh)) * 0.1
    v = _rand(rng, (b, kv, s, dh))
    length = rng.integers(1, s + 1, b).astype(np.int32)
    out_k = decode_attention(q, k, v, jnp.asarray(length), interpret=True)
    out_r = decode_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(length)
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-4, atol=2e-4)


def test_decode_attention_unpadded_length():
    """S not a TS multiple: wrapper pads; padded positions must not leak."""
    rng = np.random.default_rng(9)
    b, kv, gq, s, dh = 2, 2, 2, 700, 64
    q = _rand(rng, (b, kv, gq, dh)) * 0.1
    k = _rand(rng, (b, kv, s, dh)) * 0.1
    v = _rand(rng, (b, kv, s, dh))
    length = np.array([700, 350], np.int32)
    out_k = decode_attention(q, k, v, jnp.asarray(length), interpret=True)
    out_r = decode_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(length)
    )
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-4, atol=2e-4)


def test_decode_attention_length_one():
    rng = np.random.default_rng(10)
    q = _rand(rng, (1, 2, 4, 64))
    k = _rand(rng, (1, 2, 512, 64))
    v = _rand(rng, (1, 2, 512, 64))
    length = np.array([1], np.int32)
    out = decode_attention(q, k, v, jnp.asarray(length), interpret=True)
    # attention over a single key = that key's value
    np.testing.assert_allclose(
        np.asarray(out)[0, :, :, :], np.broadcast_to(
            np.asarray(v)[0, :, 0:1, :], (2, 4, 64)
        ), rtol=1e-4, atol=1e-4,
    )


# ----------------------------------------------------------------------
# kernel vs. engine integration
# ----------------------------------------------------------------------
def test_kernel_matches_flat_index():
    from repro.index.flat import l2_topk

    rng = np.random.default_rng(11)
    q = _rand(rng, (16, 96))
    x = _rand(rng, (2048, 96))
    mask = rng.random(2048) < 0.3
    d_k, i_k = masked_l2_topk(q, x, jnp.asarray(mask), 10, interpret=True)
    d_f, i_f = l2_topk(jnp.asarray(q), jnp.asarray(x), 10, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_f), rtol=2e-4, atol=2e-4)
