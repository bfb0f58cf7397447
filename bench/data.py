"""The benchmark's own data generator: a mixture of Gaussians at the
configuration's shape, in numpy.

It follows ``repro.data.synthetic.make_clustered`` draw for draw (centres,
Pareto cluster weights, assignment, noise, then the affine uint8 map), so a
configuration with ``data_seed`` 0 holds the vectors the repository's chip
smoke runs built on.  It is a copy and not an import: the benchmark's inputs
must not change when the program does.

The base vectors are fixed by the configuration (``data_seed``), so every run
of a cell builds and serves the same deployment and does the same work; the
run's ``--seed`` draws the query pool.

Two keys of a configuration say how its answers are judged and where its
queries come from:

- ``metric``: ``"l2"`` (squared Euclidean distance) or ``"ip"`` (inner
  product, larger is closer).  ``bench/reference.py`` and
  ``bench/check.py`` rank and judge by it.
- ``queries``, optional.  Absent, the pool is drawn from the base's own
  mixture, at its weights and spread.  ``{"source": "ood", "n_centres":
  C_q, "shift": s, "spread": σ_q, "centre_seed": c}`` draws it from another
  distribution (out-of-distribution queries, as text queries against an
  image base): ``C_q`` query centres ``(1 − s)·c_b + s·g``, each from a base
  centre ``c_b`` picked uniformly and an independent ``g ~ N(0, I)``, drawn
  from ``c`` so that the deployment fixes them; the pool is drawn from those
  centres with equal weights at spread ``σ_q``, through the base's uint8 map
  where the dtype is uint8.  A configuration that sets it lists its numbers
  under ``assumed``.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Deployment:
    data: np.ndarray  # [N, D] float32 or uint8, as the program is given it
    centers: np.ndarray  # [C, D] float32 mixture centres
    weights: np.ndarray  # [C] cluster probabilities
    spread: float
    lo: float  # affine range of the uint8 map (unused for float32)
    hi: float


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed (negative or past 64 bits
    included)."""
    return np.random.default_rng(seed % (1 << 64))


def _to_u8(x: np.ndarray, lo: float, hi: float) -> np.ndarray:
    return np.clip(np.round((x - lo) / (hi - lo) * 255), 0, 255).astype(
        np.uint8)


def deployment(cfg: dict) -> Deployment:
    """The configuration's base vectors, from its ``data_seed``."""
    a = cfg["assumed"]
    n, d, c = cfg["n"], cfg["dim"], a["n_true_clusters"]
    rng = rng_for(a["data_seed"])
    centers = rng.normal(size=(c, d)).astype(np.float32)
    weights = rng.pareto(a["pareto_shape"], c) + 0.2
    weights /= weights.sum()
    assign = rng.choice(c, size=n, p=weights)
    data = centers[assign] + a["spread"] * rng.normal(size=(n, d)).astype(
        np.float32)
    lo, hi = float(data.min()), float(data.max())
    if cfg["dtype"] == "uint8":
        data = _to_u8(data, lo, hi)
    elif cfg["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {cfg['dtype']!r}")
    return Deployment(data=data, centers=centers, weights=weights,
                      spread=float(a["spread"]), lo=lo, hi=hi)


def query_centres(dep: Deployment, spec: dict) -> np.ndarray:
    """The ``[C_q, D]`` float32 centres of an ``"ood"`` query source."""
    rng = rng_for(spec["centre_seed"])
    picks = rng.integers(0, len(dep.centers), size=spec["n_centres"])
    g = rng.normal(size=(spec["n_centres"], dep.centers.shape[1]))
    s = spec["shift"]
    return ((1.0 - s) * dep.centers[picks] + s * g).astype(np.float32)


def queries(dep: Deployment, n: int, seed: int,
            source: dict | None = None) -> np.ndarray:
    """``n`` queries drawn from ``seed``, in the deployment's dtype: from the
    deployment's mixture, or from the configuration's ``queries`` source
    (module docstring)."""
    rng = rng_for(seed)
    if source is None:
        centers, weights, spread = dep.centers, dep.weights, dep.spread
    elif source.get("source") == "ood":
        centers = query_centres(dep, source)
        weights = np.full(len(centers), 1.0 / len(centers))
        spread = float(source["spread"])
    else:
        raise ValueError(f"unknown query source {source!r}")
    assign = rng.choice(len(weights), size=n, p=weights)
    q = centers[assign] + spread * rng.normal(
        size=(n, centers.shape[1])).astype(np.float32)
    if dep.data.dtype == np.uint8:
        return _to_u8(q, dep.lo, dep.hi)
    return q.astype(np.float32)
