"""The benchmark's own data: a configuration's corpus made on the device from
a seed, and the seeded random streams every other generator draws from.

A configuration is a deployment, with one corpus: its file's ``data_seed``
draws the corpus, the predicate pool and the planner's training set, and a
run's ``--seed`` draws the traffic (arrivals, predicates of the requests,
query vectors) and the check sample.  Drawn from ``--seed``, the corpus made
the learned planner's plan mix, and so the work, differ from seed to seed
(``PERF.md``).

The corpus follows ``repro.data.vectors.make_dataset`` (a copy of its model,
kept here so that a change to the program cannot move the yardstick):
vectors from a Gaussian mixture with Dirichlet cluster weights and a spread
per cluster; categorical columns of Zipf codes, each partly taken from the
row's cluster; numeric columns normal or lognormal, some shifted by cluster.
Every number of that model comes from the configuration file.  The whole
corpus is one jitted call on the device; the same seed gives the same
arrays, so the reference regenerates it after the program has been freed.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

# independent streams drawn from one seed
STREAMS = {"data": 1, "pool": 2, "fit": 3, "schedule": 4, "prewindow": 5,
           "sample": 6, "engine": 7, "warm": 8}


def _entropy(seed: int, stream: str) -> np.random.SeedSequence:
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32, STREAMS[stream]])


def host_rng(seed: int, stream: str) -> np.random.Generator:
    """NumPy generator of one named stream of ``seed`` (any size of seed)."""
    return np.random.default_rng(_entropy(seed, stream))


def small_seed(seed: int, stream: str) -> int:
    """A 31-bit integer for APIs that take a small seed (the engine's)."""
    return int(_entropy(seed, stream).generate_state(1)[0] & 0x7FFFFFFF)


def device_key(seed: int, stream: str = "data"):
    import jax

    words = _entropy(seed, stream).generate_state(2).astype(np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _static(cfg: dict) -> tuple:
    labels = tuple((int(a["card"]), float(a["zipf"]), float(a["cluster_corr"]))
                   for a in cfg["labels"])
    numeric = tuple(tuple(sorted((k, v) for k, v in a.items())) for a in cfg["numeric"])
    return (int(cfg["rows"]), int(cfg["dim"]), int(cfg["n_clusters"]),
            float(cfg["cluster_alpha"]), tuple(map(float, cfg["spread"])),
            labels, numeric, float(cfg["query_noise"]))


@functools.lru_cache(maxsize=None)
def _generator(static: tuple):
    import jax
    import jax.numpy as jnp

    n, d, c, alpha, (s_lo, s_hi), labels, numeric, noise = static

    def gen(key):
        ks = iter(jax.random.split(key, 5 + 2 * len(labels) + len(numeric)))
        centers = jax.random.normal(next(ks), (c, d), jnp.float32)
        weights = jax.random.dirichlet(next(ks), jnp.full((c,), alpha, jnp.float32))
        cluster = jax.random.categorical(next(ks), jnp.log(weights), shape=(n,))
        spread = jax.random.uniform(next(ks), (c,), jnp.float32, s_lo, s_hi)
        x = centers[cluster] + jax.random.normal(next(ks), (n, d), jnp.float32) * spread[cluster][:, None]
        cats = []
        for card, a, corr in labels:
            logp = -a * jnp.log(jnp.arange(1, card + 1, dtype=jnp.float32))
            base = jax.random.categorical(next(ks), logp, shape=(n,))
            take = jax.random.uniform(next(ks), (n,)) < corr
            cats.append(jnp.where(take, cluster % card, base).astype(jnp.int32))
        nums = []
        for spec in numeric:
            p = dict(spec)
            z = jax.random.normal(next(ks), (n,), jnp.float32)
            shift = (cluster % int(p.get("cluster_mod", 1))) * float(p.get("cluster_step", 0.0))
            if p["dist"] == "normal":
                v = float(p.get("base", 0.0)) + shift + float(p["mean"]) + float(p["std"]) * z
            elif p["dist"] == "lognormal":
                v = float(p.get("base", 0.0)) + shift + jnp.exp(float(p["mu"]) + float(p["sigma"]) * z)
            else:
                raise ValueError(f"unknown numeric distribution {p['dist']!r}")
            nums.append(v.astype(jnp.float32))
        cat = jnp.stack(cats, axis=1) if cats else jnp.zeros((n, 0), jnp.int32)
        num = jnp.stack(nums, axis=1) if nums else jnp.zeros((n, 0), jnp.float32)
        return x, cat, num, jnp.std(x) * noise

    return jax.jit(gen)


def make_corpus(cfg: dict, seed: int):
    """Device arrays ``(vectors (N, d) f32, cat (N, A) i32, num (N, B) f32,
    query_scale)`` of the configuration, from ``seed``."""
    return _generator(_static(cfg))(device_key(seed, "data"))


def make_corpus_host(cfg: dict, seed: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """The corpus copied to the host, its device buffers released."""
    x, cat, num, qs = make_corpus(cfg, seed)
    out = np.array(x), np.array(cat), np.array(num), float(qs)
    del x, cat, num
    return out


def query_vectors(vectors: np.ndarray, scale: float, n: int,
                  rng: np.random.Generator) -> np.ndarray:
    """Perturbed corpus points (``repro.core.trainer.gen_queries``'s model):
    a random row plus Gaussian noise of ``scale`` per coordinate."""
    rows = rng.integers(vectors.shape[0], size=n)
    q = vectors[rows] + rng.normal(0.0, scale, size=(n, vectors.shape[1]))
    return q.astype(np.float32)
