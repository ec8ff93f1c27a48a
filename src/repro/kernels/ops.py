"""jit'd public wrappers around the Pallas kernels.

Handle padding/alignment, choose interpret mode off-TPU, and expose the same
signature as the :mod:`repro.kernels.ref` oracles.  ``interpret=None`` means
"auto": compiled on TPU backends, interpret elsewhere (this CPU container).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .decode_attention import TS, decode_attention_kernel
from .masked_l2 import KPAD, TN, TQ, masked_l2_topk_kernel

__all__ = [
    "masked_l2_topk", "decode_attention", "fused_masked_topk",
    "record_dispatch", "dispatch_counts", "reset_dispatch_stats", "vmem_working_set",
]

# ----------------------------------------------------------------------
# process-global dispatch ledger — the obs layer's kernel counters.
# Counts live OUTSIDE the jit'd functions (a counter inside a traced
# function only runs at trace time), in the plain-Python wrappers that
# every serving dispatch goes through: ``fused_masked_topk`` here,
# ``IVFIndex.search`` and ``BackendSet.search_class`` at their call
# sites.  Counts are deterministic per trace.  There is no timer here: a
# host clock around an asynchronous dispatch times the enqueue, not the
# work; device time comes from a profiler trace.
# ----------------------------------------------------------------------
_DISPATCH_COUNTS: Dict[str, int] = {}


def record_dispatch(name: str) -> None:
    _DISPATCH_COUNTS[name] = _DISPATCH_COUNTS.get(name, 0) + 1


def dispatch_counts() -> Dict[str, int]:
    return {k: _DISPATCH_COUNTS[k] for k in sorted(_DISPATCH_COUNTS)}


def reset_dispatch_stats() -> None:
    _DISPATCH_COUNTS.clear()


def vmem_working_set(d: int) -> dict:
    """Bytes of the fused masked top-k's pipelined VMEM blocks for one
    (query-tile, corpus-tile) step: every input and output block is double
    buffered, plus the top-k scratch.  It leaves out the compiler's own
    temporaries (the distance block, the selection loop's carries), so it
    is a lower bound, not a fit check: whether the kernel fits the chip is
    what the ahead-of-time compile in ``tests/test_tpu_compile.py`` says.
    Shared by ``benchmarks/kernel_bench.py`` and the obs snapshot
    (``repro.obs.metrics.publish_kernel_budget``)."""
    dp = -(-d // 128) * 128
    q_tile = 2 * TQ * dp * 4
    x_tile = 2 * TN * dp * 4
    mask = 2 * 8 * TN * 4            # (1, TN) f32 block, 8-sublane tile
    out = 2 * 2 * TQ * KPAD * 4
    topk_scratch = 2 * TQ * KPAD * 4
    total = q_tile + x_tile + mask + out + topk_scratch
    return {
        "q_tile": q_tile, "x_tile": x_tile, "mask": mask, "out": out,
        "scratch": topk_scratch, "total": total,
    }


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("k", "interpret"))
def masked_l2_topk(
    queries: jax.Array,  # (B, d)
    corpus: jax.Array,   # (N, d)
    mask: jax.Array,     # (N,) bool
    k: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused masked brute-force top-k. Matches masked_l2_topk_ref."""
    assert k <= KPAD, f"k={k} exceeds kernel buffer {KPAD}"
    b, d = queries.shape
    n = corpus.shape[0]
    qp = _pad_to(_pad_to(queries.astype(jnp.float32), 0, TQ), 1, 128)
    xp = _pad_to(_pad_to(corpus.astype(jnp.float32), 0, TN), 1, 128)
    mp = _pad_to(mask.astype(jnp.float32)[None, :], 1, TN, value=0.0)
    out_d, out_i = masked_l2_topk_kernel(
        qp, xp, mp, k, interpret=_auto_interpret(interpret)
    )
    return out_d[:b, :k], out_i[:b, :k]


def fused_masked_topk(
    queries: jax.Array,  # (B, d)
    corpus: jax.Array,   # (N, d)
    mask: jax.Array,     # (N,) bool
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Serving-path entry for the fused masked brute-force top-k.

    Dispatches to the Pallas kernel on TPU (one VMEM-resident sweep, the
    batched pre-filter group's hot loop) and to the jit'd XLA ``l2_topk``
    elsewhere — same contract either way: (dists (B, k), ids (B, k)),
    masked-out/short rows padded with +inf / -1.  The XLA fallback shares
    the module-level jit cache with the engine's bucket warmup, which
    pre-compiles the width-8 query shape every per-query (and small-group)
    call hits; wider pow2 batch shapes (16, 32, ...) compile once on first
    use and are cached for the rest of the process.
    """
    if jax.default_backend() == "tpu" and k <= KPAD:
        out = masked_l2_topk(queries, corpus, mask, k)
    else:
        from ..index.flat import l2_topk

        out = l2_topk(queries, corpus, k, mask)
    record_dispatch("fused_masked_topk")
    return out


@partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jax.Array,        # (B, KV, GQ, dh)
    k_cache: jax.Array,  # (B, KV, S, dh)
    v_cache: jax.Array,  # (B, KV, S, dh)
    length: jax.Array,   # (B,)
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash-decode GQA attention. Matches decode_attention_ref."""
    s = k_cache.shape[2]
    kp = _pad_to(k_cache.astype(jnp.float32), 2, TS)
    vp = _pad_to(v_cache.astype(jnp.float32), 2, TS)
    out = decode_attention_kernel(
        q.astype(jnp.float32), kp, vp, length, interpret=_auto_interpret(interpret)
    )
    return out
