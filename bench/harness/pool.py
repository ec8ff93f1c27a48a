"""The benchmark's predicates: its own representation, its own evaluation,
the generator of a traffic mix's predicate pool, and the translation into
the program's predicate objects.

The generator copies ``repro.core.trainer.gen_predicate`` (controlled
selectivity from the data's own distribution):

* ``range``  a window of the empirical CDF of one numeric column whose mass
  is the target, at a random anchor; with probability ``multi_range_prob``
  the union of two windows of half the mass each;
* ``label``  1 to 3 labels of a random anchor row (a conjunction that holds
  for at least that row);
* ``mixed``  one label of an anchor row and a window of a numeric column of
  the target's mass around the anchor's value.

Unlike the original, anchors are drawn again until a predicate passes
within a factor of two of its target (``draw``).

The kind and target selectivity of each popularity rank are drawn from the
traffic file's ``shape_seed``, the anchors from the configuration's data
seed: one pool per deployment and mix, whatever the run's seed.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple, Union

import numpy as np


@dataclasses.dataclass(frozen=True)
class Conj:
    """``AND`` of label equalities ``cat[:, a] == code`` and ranges, each a
    union of half-open intervals ``lo <= num[:, a] < hi``."""

    labels: Tuple[Tuple[int, int], ...] = ()
    ranges: Tuple[Tuple[int, Tuple[Tuple[float, float], ...]], ...] = ()

    def eval(self, cat: np.ndarray, num: np.ndarray) -> np.ndarray:
        m = np.ones(cat.shape[0] if cat.ndim == 2 else num.shape[0], bool)
        for a, code in self.labels:
            m &= cat[:, a] == code
        for a, ivs in self.ranges:
            x = num[:, a]
            r = np.zeros(x.shape[0], bool)
            for lo, hi in ivs:
                r |= (x >= lo) & (x < hi)
            m &= r
        return m


@dataclasses.dataclass(frozen=True)
class Disj:
    """``OR`` of conjunctions."""

    terms: Tuple[Conj, ...]

    def eval(self, cat: np.ndarray, num: np.ndarray) -> np.ndarray:
        m = self.terms[0].eval(cat, num)
        for t in self.terms[1:]:
            m |= t.eval(cat, num)
        return m


Pred = Union[Conj, Disj]


def to_program(p: Pred):
    """The same predicate as the program's IR (``repro.core``)."""
    from repro.core import LabelEq, Or, Predicate, RangePred

    def conj(c: Conj):
        return Predicate(labels=tuple(LabelEq(a, code) for a, code in c.labels),
                         ranges=tuple(RangePred(a, ivs) for a, ivs in c.ranges))

    if isinstance(p, Disj):
        return Or(tuple(conj(t) for t in p.terms))
    return conj(p)


def _window(xs: np.ndarray, target: float, rng) -> Tuple[float, float]:
    n = xs.size
    w = max(1, int(round(target * n)))
    lo_i = int(rng.integers(0, max(1, n - w)))
    hi_i = min(n - 1, lo_i + w)
    lo, hi = float(xs[lo_i]), float(xs[hi_i])
    return lo, (hi if hi > lo else lo + 1e-6)


def make_predicate(cat, num, sorted_num, kind: str, target: float, rng,
                   multi_range_prob: float) -> Conj:
    if kind == "range":
        a = int(rng.integers(num.shape[1]))
        if rng.random() < multi_range_prob:
            (lo1, hi1), (lo2, hi2) = (_window(sorted_num[a], target / 2, rng) for _ in range(2))
            if lo2 < hi1 and lo1 < hi2:
                ivs = ((min(lo1, lo2), max(hi1, hi2)),)
            else:
                ivs = tuple(sorted(((lo1, hi1), (lo2, hi2))))
            return Conj(ranges=((a, ivs),))
        return Conj(ranges=((a, (_window(sorted_num[a], target, rng),)),))
    row = int(rng.integers(cat.shape[0]))
    n_lbl = 1 if kind == "mixed" else int(rng.integers(1, min(3, cat.shape[1]) + 1))
    attrs = sorted(int(a) for a in rng.choice(cat.shape[1], size=n_lbl, replace=False))
    labels = tuple((a, int(cat[row, a])) for a in attrs)
    if kind == "label":
        return Conj(labels=labels)
    a = int(rng.integers(num.shape[1]))
    xs = sorted_num[a]
    pos = int(np.searchsorted(xs, float(num[row, a])))
    w = max(1, int(round(target * xs.size)))
    lo_i = max(0, pos - w // 2)
    hi_i = min(xs.size - 1, lo_i + w)
    lo, hi = float(xs[lo_i]), float(xs[hi_i])
    return Conj(labels=labels, ranges=((a, ((lo, hi if hi > lo else lo + 1e-6),)),))


def pool_shape(spec: dict) -> List[Tuple[str, float]]:
    """(kind, target selectivity) of each conjunctive pool entry, in
    popularity order, from the traffic file's ``shape_seed`` alone."""
    rng = np.random.default_rng(int(spec["shape_seed"]))
    lo, hi = spec["sel"]
    kinds = spec["kinds"]
    return [(kinds[int(rng.integers(len(kinds)))],
             float(np.exp(rng.uniform(np.log(lo), np.log(hi)))))
            for _ in range(int(spec["n"]))]


def _sorted_columns(num: np.ndarray) -> List[np.ndarray]:
    return [np.sort(num[:, j]) for j in range(num.shape[1])]


def draw(cat, num, shape: Sequence[Tuple[str, float]], rng,
         multi_range_prob: float, tries: int = 32) -> List[Conj]:
    """One predicate per (kind, target), anchored by ``rng``.  Anchors are
    drawn again until the predicate's selectivity lies within a factor of two
    of its target (controlled selectivity, paper Sec. 3.1; label and mixed
    predicates otherwise miss their target by orders of magnitude), keeping
    the closest of ``tries`` draws; a predicate no row passes never counts."""
    sorted_num = _sorted_columns(num)
    n = cat.shape[0] if cat.ndim == 2 else num.shape[0]
    out = []
    for kind, target in shape:
        best, best_err = None, np.inf
        for _ in range(tries):
            p = make_predicate(cat, num, sorted_num, kind, target, rng, multi_range_prob)
            sel = p.eval(cat, num).sum() / n
            err = abs(np.log(sel / target)) if sel > 0 else np.inf
            if err < best_err:
                best, best_err = p, err
            if err <= np.log(2.0):
                break
        if best is None:
            raise ValueError(f"no {kind} predicate near selectivity {target} passes any row")
        out.append(best)
    return out


def make_pool(cat, num, spec: dict, rng) -> List[Pred]:
    """The traffic mix's predicate pool, most popular first: ``n``
    conjunctions, then ``n_or`` ORs of two of them (fixed pairs, from the
    shape seed)."""
    conj = draw(cat, num, pool_shape(spec), rng, float(spec.get("multi_range_prob", 0.2)))
    pair_rng = np.random.default_rng(int(spec["shape_seed"]) + 1)
    pairs = [pair_rng.choice(len(conj), size=2, replace=False)
             for _ in range(int(spec.get("n_or", 0)))]
    return conj + [Disj((conj[int(i)], conj[int(j)])) for i, j in pairs]
