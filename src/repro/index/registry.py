"""Pluggable ANN-backend registry — the engine's (plan, backend, knob) space.

The planner is generic over backends (the paper's claim); this module makes
that concrete.  Every backend exposes one uniform surface:

* ``build(corpus)``                     — construct from an (N, d) float32 corpus
* ``search_masked(queries, mask, k, knobs)`` — masked top-k, mask applied
  DURING the search (no filtered-out id may ever surface)
* ``memory_bytes()``                    — scan-resident footprint
* ``knob_grid()``                       — declared :class:`KnobTier` list; each
  tier names a knob setting and the recall floor it promises

and must satisfy the cross-backend conformance harness
(``tests/backend_conformance.py``): recall floors at every declared tier,
bit-stable row independence in any batch composition (the PR 2 discipline),
mask/tombstone safety, empty/tiny/all-masked edges, and sharded ≡ unsharded
merge identity.  A fifth backend is one :func:`register_backend` call plus a
green conformance run.

Registered by default: ``flat`` (exact masked scan), ``ivf`` (IVF-Flat probe
scan), ``ivfpq`` (:class:`~repro.index.pq.IVFPQIndex`, int8 ADC + exact
re-rank), ``acorn`` (predicate-aware graph traversal).

Corpora below ``TINY_N`` points degenerate every approximate backend to the
exact masked scan: cluster structure is meaningless at that size and the
edge-case contract (every passing point returned when ``|masked| <= k``)
must hold for all backends.

:class:`BackendSet` is what the engine holds: one built instance per backend
with the flattened ``classes()`` enumeration ``[(backend, tier), ...]`` that
the planner's routing head indexes into.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, Mapping, Optional, Protocol, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .flat import l2_topk
from .ivf import IVFIndex
from .acorn import AcornIndex
from .pq import IVFPQIndex

__all__ = [
    "KnobTier",
    "SearchBackend",
    "BackendSet",
    "LiveIndex",
    "register_backend",
    "unregister_backend",
    "backend_names",
    "make_backend",
    "DEFAULT_BACKENDS",
    "TINY_N",
]

# below this corpus size every backend falls back to the exact masked scan
TINY_N = 64


@dataclass(frozen=True)
class KnobTier:
    """One named knob setting with the recall@10 floor it declares.

    The floor is a *contract*: the conformance harness measures masked
    recall@10 against the exact oracle at this tier and fails the backend if
    it undershoots.  The engine's routing classes are (backend, tier) pairs.
    """
    name: str
    knobs: Mapping[str, int] = field(default_factory=dict)
    recall_floor: float = 0.5


class SearchBackend(Protocol):
    """Uniform backend surface; see module docstring for the contract."""

    name: str

    def build(self, corpus: np.ndarray) -> "SearchBackend": ...

    def search_masked(
        self,
        queries: np.ndarray,
        mask: Optional[np.ndarray],
        k: int,
        knobs: Optional[Mapping[str, int]] = None,
    ) -> Tuple[np.ndarray, np.ndarray]: ...

    def memory_bytes(self) -> int: ...

    def knob_grid(self) -> Tuple[KnobTier, ...]: ...


# ----------------------------------------------------------------------
# shared helpers
# ----------------------------------------------------------------------
def _empty_result(b: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
    return np.full((b, k), np.inf, np.float32), np.full((b, k), -1, np.int32)


def _exact_masked(
    vectors: np.ndarray, queries: np.ndarray, mask: Optional[np.ndarray], k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact masked top-k in pure numpy with composite tie keys.  Every row
    is an independent broadcast/reduce, so results are batch-invariant by
    construction — the tiny-corpus fallback for all backends."""
    q = np.atleast_2d(np.asarray(queries, np.float32))
    b = q.shape[0]
    out_d, out_i = _empty_result(b, k)
    n = vectors.shape[0]
    if n == 0:
        return out_d, out_i
    d2 = ((q[:, None, :] - vectors[None]) ** 2).sum(-1).astype(np.float32)
    d2 = np.maximum(d2, 0.0)
    if mask is not None:
        d2 = np.where(np.asarray(mask, bool)[None, :], d2, np.inf)
    key = (d2.view(np.int32).astype(np.int64) << 32) | np.arange(n, dtype=np.int64)[None]
    kk = min(k, n)
    sel = np.argsort(key, axis=1, kind="stable")[:, :kk]
    sd = np.take_along_axis(d2, sel, axis=1)
    fin = np.isfinite(sd)
    out_d[:, :kk] = np.where(fin, sd, np.inf)
    out_i[:, :kk] = np.where(fin, sel.astype(np.int32), -1)
    return out_d, out_i


# ----------------------------------------------------------------------
# backend adapters
# ----------------------------------------------------------------------
class FlatBackend:
    """Exact masked scan — the recall ceiling and memory baseline."""

    name = "flat"

    def __init__(self, seed: int = 0):
        self.seed = seed

    def build(self, corpus: np.ndarray) -> "FlatBackend":
        self.vectors = np.ascontiguousarray(corpus, np.float32)
        self.n, self.dim = self.vectors.shape
        self._vecs_j = jnp.asarray(self.vectors) if self.n else None
        return self

    def search_masked(self, queries, mask, k, knobs=None):
        q = np.atleast_2d(np.asarray(queries, np.float32))
        b = q.shape[0]
        out_d, out_i = _empty_result(b, k)
        if self.n == 0:
            return out_d, out_i
        if self.n < TINY_N:
            return _exact_masked(self.vectors, q, mask, k)
        kk = min(k, self.n)
        mask_j = None if mask is None else jnp.asarray(np.asarray(mask, bool))
        # fixed (8, d) query blocks: the same GEMM shape for any batch size,
        # so each row's result is independent of its batch composition
        q8 = np.zeros((8, self.dim), np.float32)
        for s in range(0, b, 8):
            e = min(b, s + 8)
            q8[:] = 0.0
            q8[: e - s] = q[s:e]
            d_, i_ = l2_topk(jnp.asarray(q8), self._vecs_j, kk, mask_j)
            out_d[s:e, :kk] = np.asarray(d_)[: e - s]
            out_i[s:e, :kk] = np.asarray(i_)[: e - s]
        return out_d, out_i

    def memory_bytes(self) -> int:
        return int(self.vectors.nbytes)

    def knob_grid(self) -> Tuple[KnobTier, ...]:
        return (KnobTier("exact", {}, recall_floor=0.99),)


class IVFBackend:
    """IVF-Flat probe-list scan (wraps :class:`IVFIndex`)."""

    name = "ivf"

    def __init__(self, n_lists: Optional[int] = None, seed: int = 0):
        self.n_lists = n_lists
        self.seed = seed

    def build(self, corpus: np.ndarray) -> "IVFBackend":
        self.vectors = np.ascontiguousarray(corpus, np.float32)
        self.n = self.vectors.shape[0]
        self.index = (
            IVFIndex(self.vectors, n_lists=self.n_lists, seed=self.seed).build()
            if self.n >= TINY_N else None
        )
        return self

    def search_masked(self, queries, mask, k, knobs=None):
        q = np.atleast_2d(np.asarray(queries, np.float32))
        if self.index is None:
            return _exact_masked(self.vectors, q, mask, k)
        nprobe = int((knobs or {}).get("nprobe", 8))
        return self.index.search(q, k, nprobe=nprobe,
                                 mask=None if mask is None else np.asarray(mask, bool))

    def memory_bytes(self) -> int:
        if self.index is None:
            return int(self.vectors.nbytes)
        ix = self.index
        return int(ix.sorted_vecs.nbytes + ix.centroids.nbytes + ix.sorted_ids.nbytes
                   + ix.offsets.nbytes + ix.sorted_sq.nbytes + ix.padded_ids.nbytes)

    def knob_grid(self) -> Tuple[KnobTier, ...]:
        return (
            KnobTier("fast", {"nprobe": 8}, recall_floor=0.50),
            KnobTier("balanced", {"nprobe": 16}, recall_floor=0.70),
            KnobTier("precise", {"nprobe": 64}, recall_floor=0.90),
        )


class IVFPQBackend:
    """IVF-PQ int8 ADC scan with exact re-rank (wraps :class:`IVFPQIndex`)."""

    name = "ivfpq"

    def __init__(self, n_lists: Optional[int] = None, m: Optional[int] = None,
                 seed: int = 0):
        self.n_lists = n_lists
        self.m = m
        self.seed = seed

    def build(self, corpus: np.ndarray) -> "IVFPQBackend":
        self.vectors = np.ascontiguousarray(corpus, np.float32)
        self.n = self.vectors.shape[0]
        self.index = (
            IVFPQIndex(self.vectors, n_lists=self.n_lists, m=self.m,
                       seed=self.seed).build()
            if self.n >= TINY_N else None
        )
        return self

    def search_masked(self, queries, mask, k, knobs=None):
        q = np.atleast_2d(np.asarray(queries, np.float32))
        if self.index is None:
            return _exact_masked(self.vectors, q, mask, k)
        kn = knobs or {}
        return self.index.search(
            q, k,
            nprobe=int(kn.get("nprobe", 8)),
            rerank=int(kn.get("rerank", 64)),
            mask=None if mask is None else np.asarray(mask, bool),
        )

    def memory_bytes(self) -> int:
        if self.index is None:
            return int(self.vectors.nbytes)
        return self.index.memory_bytes()

    @property
    def rerank_bytes(self) -> int:
        return 0 if self.index is None else self.index.rerank_bytes

    def knob_grid(self) -> Tuple[KnobTier, ...]:
        return (
            KnobTier("fast", {"nprobe": 8, "rerank": 32}, recall_floor=0.45),
            KnobTier("precise", {"nprobe": 64, "rerank": 256}, recall_floor=0.80),
        )


class AcornBackend:
    """ACORN-1 predicate-aware graph traversal (wraps :class:`AcornIndex`)."""

    name = "acorn"

    def __init__(self, m: int = 24, seed: int = 0):
        self.m = m
        self.seed = seed

    def build(self, corpus: np.ndarray) -> "AcornBackend":
        self.vectors = np.ascontiguousarray(corpus, np.float32)
        self.n = self.vectors.shape[0]
        self.index = (
            AcornIndex(self.vectors, m=self.m, seed=self.seed).build()
            if self.n >= TINY_N else None
        )
        return self

    def search_masked(self, queries, mask, k, knobs=None):
        q = np.atleast_2d(np.asarray(queries, np.float32))
        if self.index is None:
            return _exact_masked(self.vectors, q, mask, k)
        ef = int((knobs or {}).get("ef", 64))
        return self.index.search(q, k, ef=ef,
                                 mask=None if mask is None else np.asarray(mask, bool))

    def memory_bytes(self) -> int:
        if self.index is None:
            return int(self.vectors.nbytes)
        ix = self.index
        return int(self.vectors.nbytes + ix.neighbors.nbytes + ix.seeds.nbytes)

    def knob_grid(self) -> Tuple[KnobTier, ...]:
        return (
            KnobTier("fast", {"ef": 64}, recall_floor=0.45),
            KnobTier("precise", {"ef": 160}, recall_floor=0.70),
        )


class LiveIndex:
    """Mutation-aware view over one BUILT backend: composes a
    :class:`~repro.core.corpus.LiveCorpus`'s tombstones into every mask and
    merges an exact scan of the append segment into the backend's base
    results — so any registered backend serves a mutated corpus without a
    rebuild.  Satisfies the same ``search_masked`` surface (and the same
    conformance contract: a tombstoned id can never surface; declared
    recall floors hold over the LIVE rows).

    The segment scan uses the same fused ``l2_topk`` kernel as the exact
    executors, keeping per-row distances bit-identical to what a fresh
    build over the compacted corpus would compute (the PR 2 discipline) —
    that is what makes compaction id-stable for exact tiers.
    """

    def __init__(self, base: SearchBackend, live):
        self.base = base
        self.live = live
        self.name = base.name

    def build(self, corpus: np.ndarray) -> "LiveIndex":
        self.base.build(corpus)
        return self

    def search_masked(self, queries, mask, k, knobs=None):
        q = np.atleast_2d(np.asarray(queries, np.float32))
        live = self.live
        base_n = live.base_n
        alive = live.alive_mask()
        if mask is None:
            bmask = alive[:base_n]
            smask = alive[base_n:]
        else:
            m = np.asarray(mask, bool)
            if m.size == live.n_total:
                bmask = m[:base_n] & alive[:base_n]
                smask = m[base_n:] & alive[base_n:]
            else:
                # base-length mask: the caller predates the segment, so
                # segment rows are filtered by liveness alone
                bmask = m & alive[:base_n]
                smask = alive[base_n:]
        bd, bi = self.base.search_masked(q, bmask, k, knobs=knobs)
        bd, bi = np.asarray(bd), np.asarray(bi)
        if live.seg_n and smask.any():
            from ..dist.collectives import merge_topk

            kk = min(k, live.seg_n)
            sd, si = l2_topk(q, live.seg_vectors(), kk, smask)
            sd, si = np.asarray(sd), np.asarray(si)
            si = np.where(si >= 0, si + base_n, -1).astype(np.int32)
            # base part first: merge_topk's column tie-break then preserves
            # handle order, the compaction bit-identity argument
            bd, bi = merge_topk([bd, sd], [bi, si], k)
        return bd, bi

    def memory_bytes(self) -> int:
        seg = self.live.seg_vectors().nbytes if self.live.seg_n else 0
        return int(self.base.memory_bytes() + seg + self.live.tomb.nbytes)

    def knob_grid(self) -> Tuple[KnobTier, ...]:
        return self.base.knob_grid()


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
_REGISTRY: "OrderedDict[str, Callable[..., SearchBackend]]" = OrderedDict()


def register_backend(name: str, factory: Callable[..., SearchBackend],
                     overwrite: bool = False) -> None:
    """Register ``factory(seed=...) -> SearchBackend`` under ``name``."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _REGISTRY[name] = factory


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def backend_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def make_backend(name: str, corpus: np.ndarray, seed: int = 0) -> SearchBackend:
    """Construct and build a registered backend over ``corpus``."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; registered: {backend_names()}")
    return _REGISTRY[name](seed=seed).build(np.asarray(corpus, np.float32))


register_backend("flat", FlatBackend)
register_backend("ivf", IVFBackend)
register_backend("ivfpq", IVFPQBackend)
register_backend("acorn", AcornBackend)

DEFAULT_BACKENDS: Tuple[str, ...] = ("flat", "ivf", "ivfpq", "acorn")


# ----------------------------------------------------------------------
# BackendSet — what the engine holds
# ----------------------------------------------------------------------
class BackendSet:
    """Built backend instances plus the flattened (backend, tier) routing
    classes the planner's routing head indexes into.  Class order is the
    registration order of backends crossed with each backend's declared
    tier order — deterministic, so a routing label is stable across runs."""

    def __init__(self, backends: "OrderedDict[str, SearchBackend]"):
        self.backends = backends
        self._classes: Tuple[Tuple[str, str], ...] = tuple(
            (bname, tier.name)
            for bname, b in backends.items()
            for tier in b.knob_grid()
        )
        self._knobs: Tuple[Mapping[str, int], ...] = tuple(
            tier.knobs
            for b in backends.values()
            for tier in b.knob_grid()
        )
        self._floors: Tuple[float, ...] = tuple(
            tier.recall_floor
            for b in backends.values()
            for tier in b.knob_grid()
        )

    @classmethod
    def build(cls, corpus: np.ndarray, names: Optional[Sequence[str]] = None,
              seed: int = 0) -> "BackendSet":
        names = tuple(names) if names else DEFAULT_BACKENDS
        built = OrderedDict(
            (nm, make_backend(nm, corpus, seed=seed)) for nm in names
        )
        return cls(built)

    def classes(self) -> Tuple[Tuple[str, str], ...]:
        return self._classes

    def class_names(self) -> Tuple[str, ...]:
        return tuple(f"{b}:{t}" for b, t in self._classes)

    def recall_floor(self, ci: int) -> float:
        return self._floors[ci]

    def search_class(self, ci: int, queries: np.ndarray,
                     mask: Optional[np.ndarray], k: int):
        bname, _ = self._classes[ci]
        from ..kernels.ops import record_dispatch

        out = self.backends[bname].search_masked(queries, mask, k,
                                                 knobs=self._knobs[ci])
        record_dispatch(f"backend_{bname}")
        return out

    def memory_bytes(self) -> Dict[str, int]:
        return {nm: b.memory_bytes() for nm, b in self.backends.items()}
