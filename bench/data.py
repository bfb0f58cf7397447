"""The benchmark's own data generator: a mixture of Gaussians at the
configuration's shape, in numpy.

It follows ``repro.data.synthetic.make_clustered`` draw for draw (centres,
Pareto cluster weights, assignment, noise, then the affine uint8 map), so a
configuration with ``data_seed`` 0 holds the vectors the repository's chip
smoke runs built on.  It is a copy and not an import: the benchmark's inputs
must not change when the program does.

The base vectors are fixed by the configuration (``data_seed``), so every run
of a cell builds and serves the same deployment and does the same work; the
run's ``--seed`` draws the query pool from the same mixture.
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


def queries(dep: Deployment, n: int, seed: int) -> np.ndarray:
    """``n`` queries from the deployment's mixture, drawn from ``seed``, in
    the deployment's dtype."""
    rng = rng_for(seed)
    assign = rng.choice(len(dep.weights), size=n, p=dep.weights)
    q = dep.centers[assign] + dep.spread * rng.normal(
        size=(n, dep.centers.shape[1])).astype(np.float32)
    if dep.data.dtype == np.uint8:
        return _to_u8(q, dep.lo, dep.hi)
    return q.astype(np.float32)
