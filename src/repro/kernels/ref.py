"""Pure-jnp oracles for the Pallas kernels (the correctness contracts)."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["masked_l2_topk_ref", "decode_attention_ref"]

BIG = jnp.float32(3.4e38)  # stand-in for +inf that survives arithmetic


@partial(jax.jit, static_argnames=("k",))
def masked_l2_topk_ref(
    queries: jax.Array,  # (B, d) f32
    corpus: jax.Array,   # (N, d) f32
    mask: jax.Array,     # (N,) bool / {0,1}
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Exact masked top-k by squared L2.  Masked-out -> dist BIG, id -1."""
    q2 = jnp.sum(queries * queries, axis=1, keepdims=True)
    x2 = jnp.sum(corpus * corpus, axis=1)
    qx = jnp.matmul(queries, corpus.T, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(q2 + x2[None, :] - 2.0 * qx, 0.0)
    d2 = jnp.where(mask.astype(bool)[None, :], d2, BIG)
    neg, idx = jax.lax.top_k(-d2, k)
    d = -neg
    return d, jnp.where(d >= BIG, -1, idx)


@partial(jax.jit, static_argnames=())
def decode_attention_ref(
    q: jax.Array,        # (B, KV, GQ, dh)  one new token, grouped heads
    k_cache: jax.Array,  # (B, KV, S, dh)
    v_cache: jax.Array,  # (B, KV, S, dh)
    length: jax.Array,   # (B,) valid KV length per sequence
) -> jax.Array:
    """GQA decode attention over a (padded) KV cache; returns (B, KV, GQ, dh)."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = jnp.einsum("bkgd,bksd->bkgs", q, k_cache) * scale
    s = k_cache.shape[2]
    pos = jnp.arange(s)[None, None, None, :]
    valid = pos < length[:, None, None, None]
    scores = jnp.where(valid, scores, -BIG)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", w, v_cache)
