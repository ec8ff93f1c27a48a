"""Flash-decode GQA attention Pallas kernel (one new token vs. a long KV).

The decode_32k / long_500k serving cells are bound by exactly this op: the
entire KV cache must stream HBM->VMEM once per decoded token, so the kernel's
job is to (a) touch each KV byte exactly once and (b) keep the online-softmax
state (running max, denominator, weighted accumulator) resident in VMEM.

Layout: q (B, KV, GQ, dh) one token of GQ=HQ/KV grouped query heads per KV
head; caches (B, KV, S, dh).  Grid: (B, KV, S_tiles) — S minor so the
softmax state persists across the KV sweep for one (batch, kv-head).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_kernel", "TS"]

TS = 512  # KV tile length
NEG = -3.4e38  # python float: jnp constants would be captured consts in pallas


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, n_tiles: int):
    """q_ref: (1, 1, GQ, dh); k/v_ref: (1, 1, TS, dh); o_ref: (1, 1, GQ, dh)
    scratch: m (GQ, 128) running max, l (GQ, 128) denominator, acc (GQ, dh)."""
    s_idx = pl.program_id(2)
    b_idx = pl.program_id(0)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0, 0]                                   # (GQ, dh) — this (b, kv) block
    k = k_ref[0, 0]                                   # (TS, dh)
    v = v_ref[0, 0]                                   # (TS, dh)
    gq, dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(dh))

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (GQ, TS)
    pos = s_idx * TS + jax.lax.broadcasted_iota(jnp.int32, (gq, TS), 1)
    valid = pos < len_ref[b_idx, 0]
    scores = jnp.where(valid, scores, NEG)

    m_prev = m_ref[:, 0]                              # (GQ,)
    m_new = jnp.maximum(m_prev, scores.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)                   # rescale old state
    p = jnp.exp(scores - m_new[:, None])              # (GQ, TS)
    l_new = l_ref[:, 0] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(s_idx == n_tiles - 1)
    def _flush():
        l = l_ref[:, 0]
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]


def decode_attention_kernel(
    q: jax.Array,        # (B, KV, GQ, dh) f32
    k_cache: jax.Array,  # (B, KV, S, dh) f32, S % TS == 0
    v_cache: jax.Array,  # (B, KV, S, dh) f32
    length: jax.Array,   # (B,) int32
    *,
    interpret: bool = False,
) -> jax.Array:
    b, kv, gq, dh = q.shape
    s = k_cache.shape[2]
    assert s % TS == 0, s
    grid = (b, kv, s // TS)
    kernel = functools.partial(_kernel, n_tiles=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, 1), lambda i, j, t: (0, 0)),                # lengths
            pl.BlockSpec((1, 1, gq, dh), lambda i, j, t: (i, j, 0, 0)),  # q stays
            pl.BlockSpec((1, 1, TS, dh), lambda i, j, t: (i, j, t, 0)),
            pl.BlockSpec((1, 1, TS, dh), lambda i, j, t: (i, j, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gq, dh), lambda i, j, t: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, gq, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((gq, 128), jnp.float32),
            pltpu.VMEM((gq, 128), jnp.float32),
            pltpu.VMEM((gq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(length.reshape(b, 1).astype(jnp.int32), q, k_cache, v_cache)
