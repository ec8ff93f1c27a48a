"""The plain reference that decides ``correct``, and its lower-precision
control.

Semantics (the configuration files state them): an exact plan returns the
exact masked top-k by squared L2 distance over float32 vectors; an
approximate (post-filter) plan returns ids that pass the predicate, each at
most once, and meets the configuration's recall bar.

The reference imports nothing of the program.  It evaluates the benchmark's
own predicates on the host, gathers the passing rows of a corpus that it
regenerates on the device from the seed, ranks them with a HIGHEST-precision
matmul, and refines the best ``k + REFINE`` by the difference form
``sum((x - q)^2)``, in blocks of one predicate and at most 64 queries.  The
control is the same scan with bfloat16 operands and no refinement: the step
below float32 that would tempt a faster kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

REFINE = 8          # candidates beyond k whose distances are refined
TIE_RTOL = 1e-5     # a served id this close to the k-th distance is a hit
BAD_GAP = 1e9       # the gap of an exact answer that is wrong in kind
Q_BLOCK = 64


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _kernels():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("kc", "low"))
    def topk_subset(x, idx, valid, q, kc, low):
        rows = jnp.take(x, idx, axis=0)
        if low:
            qx = jnp.matmul(q.astype(jnp.bfloat16), rows.astype(jnp.bfloat16).T,
                            preferred_element_type=jnp.float32)
        else:
            qx = jnp.matmul(q, rows.T, precision=jax.lax.Precision.HIGHEST)
        d = (jnp.sum(q * q, 1)[:, None] + jnp.sum(rows * rows, 1)[None, :] - 2.0 * qx)
        d = jnp.where(valid[None, :], jnp.maximum(d, 0.0), jnp.inf)
        neg, pos = jax.lax.top_k(-d, kc)
        return -neg, jnp.where(jnp.isinf(neg), -1, idx[pos])

    @jax.jit
    def exact_dists(x, ids, q):
        rows = jnp.take(x, jnp.maximum(ids, 0), axis=0)
        d = jnp.sum((rows - q[:, None, :]) ** 2, axis=-1)
        return jnp.where(ids >= 0, d, jnp.inf)

    return topk_subset, exact_dists


def exact_distances(x_dev, ids: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Difference-form float32 distances of ``ids`` (B, m) to ``queries``."""
    _, exact_dists = _kernels()
    out = np.empty(ids.shape, np.float32)
    for s in range(0, ids.shape[0], Q_BLOCK):
        e = min(ids.shape[0], s + Q_BLOCK)
        qb = _pow2(e - s, 8)
        ip = np.full((qb, ids.shape[1]), -1, np.int32)
        ip[:e - s] = ids[s:e]
        qp = np.zeros((qb, queries.shape[1]), np.float32)
        qp[:e - s] = queries[s:e]
        out[s:e] = np.asarray(exact_dists(x_dev, ip, qp))[:e - s]
    return out


def topk(x_dev, masks: Dict[int, np.ndarray], pred_of: np.ndarray,
         queries: np.ndarray, k: int, low: bool = False):
    """Masked top-k of every query (request ``i`` under ``masks[pred_of[i]]``).

    ``low=False`` is the reference: ids and difference-form distances,
    ascending by (distance, id).  ``low=True`` is the control: the top k of
    the bfloat16 scan, as a program that computed in bfloat16 would serve it.
    Returns ``(ids (R, k) -1 padded, dists (R, k) inf padded)``."""
    topk_subset, _ = _kernels()
    r = pred_of.size
    ids = np.full((r, k), -1, np.int32)
    dists = np.full((r, k), np.inf, np.float32)
    for p in np.unique(pred_of):
        rows = np.flatnonzero(pred_of == p)
        idx = np.flatnonzero(masks[int(p)]).astype(np.int32)
        if idx.size == 0:
            continue
        n_p = _pow2(idx.size, 1024)
        idx_p = np.zeros(n_p, np.int32)
        idx_p[:idx.size] = idx
        valid = np.zeros(n_p, bool)
        valid[:idx.size] = True
        kc = k if low else k + REFINE
        for s in range(0, rows.size, Q_BLOCK):
            blk = rows[s:s + Q_BLOCK]
            qb = _pow2(blk.size, 8)
            qp = np.zeros((qb, queries.shape[1]), np.float32)
            qp[:blk.size] = queries[blk]
            _, cand = topk_subset(x_dev, idx_p, valid, qp, kc=kc, low=low)
            cand = np.asarray(cand)[:blk.size]
            if low:
                ids[blk] = cand
                continue
            d = exact_distances(x_dev, cand, queries[blk])
            for j, i in enumerate(blk):
                ok = cand[j] >= 0
                c, dd = cand[j][ok], d[j][ok]
                order = np.lexsort((c, dd))[:k]
                ids[i, :order.size] = c[order]
                dists[i, :order.size] = dd[order]
    return ids, dists


@dataclasses.dataclass
class Readings:
    exact_gap: float            # widest relative gap of an exact-plan answer
    post_recall: Optional[float]  # mean recall@k of approximate-plan answers
    wrong: int                  # unanswered, failing the predicate, duplicated
    recall: float               # mean recall@k over every compared request
    n_exact: int
    n_post: int


def score(served_ids: np.ndarray, served_d: np.ndarray, answered: np.ndarray,
          exact: np.ndarray, masks: Dict[int, np.ndarray], pred_of: np.ndarray,
          ref_ids: np.ndarray, ref_d: np.ndarray) -> Readings:
    """Compare served answers with the reference, request by request.

    ``served_d`` holds the reference's own (difference-form) distances of the
    served ids.  An exact-plan answer's gap is how far its farthest id lies
    beyond the reference's k-th distance, relative to it; an answer that
    never came, or has a duplicate, an id that fails the predicate, or fewer
    ids than the reference has, counts as a gap of ``BAD_GAP``."""
    gap, post_rec, rec, wrong = 0.0, [], [], 0
    n_exact = 0
    for i in range(pred_of.size):
        if not answered[i]:
            wrong += 1
            rec.append(0.0)
            if exact[i]:
                gap = BAD_GAP
            continue
        mask = masks[int(pred_of[i])]
        n_ref = int((ref_ids[i] >= 0).sum())
        sel = served_ids[i] >= 0
        got, gd = served_ids[i][sel], served_d[i][sel]
        bad = len(np.unique(got)) != got.size or not mask[got].all()
        wrong += int(bad)
        kth = float(ref_d[i][n_ref - 1]) if n_ref else 0.0
        rel = (gd - kth) / max(kth, 1e-12)
        hits = min(n_ref, int(((rel <= TIE_RTOL) & mask[got]).sum())) if not bad else 0
        r_i = hits / n_ref if n_ref else float(got.size == 0)
        rec.append(r_i)
        if exact[i]:
            n_exact += 1
            g = BAD_GAP if (bad or got.size < n_ref) else float(max(rel.max(initial=0.0), 0.0))
            gap = max(gap, g)
        else:
            post_rec.append(r_i)
    return Readings(gap, float(np.mean(post_rec)) if post_rec else None, wrong,
                    float(np.mean(rec)) if rec else 0.0, n_exact, len(post_rec))


def masks_for(preds: Sequence, cat: np.ndarray, num: np.ndarray,
              used: np.ndarray) -> Dict[int, np.ndarray]:
    return {int(p): preds[int(p)].eval(cat, num) for p in np.unique(used)}


def checks(readings: Readings, limits: dict) -> List[tuple]:
    """(name, value, limit, ok) of every number compared."""
    out = [("exact_gap", readings.exact_gap, limits["exact_gap"],
            readings.exact_gap <= limits["exact_gap"]),
           ("wrong_answers", readings.wrong, 0, readings.wrong == 0)]
    if readings.post_recall is not None:
        out.append(("post_recall", readings.post_recall, limits["post_recall"],
                    readings.post_recall >= limits["post_recall"]))
    return out
