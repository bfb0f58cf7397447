"""End-to-end index construction drivers for all four compared systems
(paper §VI): ScaleGANN, DiskANN, Extended CAGRA, GGNN.

Each driver returns a :class:`BuildResult` with the paper's two timing
metrics — **overall** (partition + shard build + merge) and **build-only**
(shard indexing only) — plus per-shard build times that feed the
multi-instance scheduler simulation (Table VII) and the cost model (§VI-C).

Shard builds execute on a thread pool of ``n_workers`` — the software analog
of "each available GPU instance is assigned an independent shard-level
indexing task" (no inter-worker communication, §IV).  Wall-clock numbers on
this CPU container are *relative* (the paper's conclusions are all ratios).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


class ShardBuildError(RuntimeError):
    """One or more shard builds failed after exhausting their retries.

    ``errors`` maps shard index → the final exception; ``attempts`` maps
    shard index → how many attempts that shard consumed.  Successful
    shards' work is *not* discarded by the raising path — the exception
    surfaces everything the caller needs to diagnose or re-drive the
    failed shards.
    """

    def __init__(self, errors: dict, attempts: dict):
        self.errors = dict(errors)
        self.attempts = dict(attempts)
        detail = "; ".join(
            f"shard {i}: {type(e).__name__}: {e} "
            f"(after {attempts.get(i, '?')} attempts)"
            for i, e in sorted(errors.items())
        )
        super().__init__(
            f"{len(errors)} shard build(s) failed after retries — {detail}"
        )

from repro.configs.base import IndexConfig
from repro.core import cagra, vamana
from repro.core.merge import GlobalIndex, merge_shard_indexes
from repro.core.partition import PartitionResult, Shard, partition
from repro.telemetry import NULL_SPAN, current_tracer

BUILDERS = {
    "cagra": cagra.build_shard_index,
    "vamana": vamana.build_shard_index_vamana,
}

# seed-loop baselines: the pre-vectorization hot loops, kept for
# bench_build.py's before/after comparison and the parity tests
REFERENCE_BUILDERS = {
    "cagra": functools.partial(cagra.build_shard_index, reference=True),
    "vamana": vamana.build_shard_index_vamana_sequential,
}


@dataclasses.dataclass
class BuildResult:
    name: str
    index: GlobalIndex | None  # merged systems only
    shards: list[Shard]
    shard_graphs: list[np.ndarray]
    partition_s: float
    build_only_s: float  # Σ shard build time (1-worker equivalent)
    wall_build_s: float  # elapsed with n_workers
    merge_s: float
    per_shard_s: list[float]
    n_distance_computations: int
    stats: dict
    metric: str  # the IndexConfig.metric built with; serving defaults to it
    centroids: np.ndarray | None = None  # [n_shards, D] partition centroids
    shard_attempts: list[int] | None = None  # per-shard build attempts
    shard_errors: list[str] | None = None  # per-shard last retried error

    @property
    def overall_s(self) -> float:
        return self.partition_s + self.wall_build_s + self.merge_s

    def feed_metrics(self, registry=None):
        """Feed this build's aggregates into a
        :class:`~repro.telemetry.MetricsRegistry` (a fresh one by
        default) and return it — the build result is the source of truth,
        the registry is its exposition, so dashboards and the Prometheus
        text format come for free instead of each bench re-deriving them.
        Metrics are labeled by ``system`` so several compared builds can
        share one registry."""
        from repro.telemetry import MetricsRegistry

        reg = registry if registry is not None else MetricsRegistry()
        sys_ = self.name
        reg.counter("build_shards_total", "shards built",
                    system=sys_).inc(len(self.shards))
        reg.counter("build_distance_computations_total",
                    "distance computations spent building",
                    system=sys_).inc(self.n_distance_computations)
        if self.shard_attempts:
            reg.counter("build_shard_attempts_total",
                        "shard build attempts including retries",
                        system=sys_).inc(sum(self.shard_attempts))
        phase = "build_phase_seconds"
        phelp = "wall seconds per build phase"
        reg.gauge(phase, phelp, system=sys_,
                  phase="partition").set(self.partition_s)
        reg.gauge(phase, phelp, system=sys_,
                  phase="shards").set(self.wall_build_s)
        reg.gauge(phase, phelp, system=sys_,
                  phase="merge").set(self.merge_s)
        reg.gauge("build_overall_seconds", "partition + shards + merge",
                  system=sys_).set(self.overall_s)
        h = reg.histogram("build_shard_seconds",
                          "per-shard build wall time", system=sys_)
        for s in self.per_shard_s:
            h.observe(s)
        return reg

    def topology(self, data: np.ndarray, *, metric: str | None = None):
        """The search topology this build serves: merged systems expose the
        global graph, split-only systems the centroid-routed shard path
        (``repro.search.search(..., nprobe=...)`` prunes which shards each
        query visits).  ``metric`` defaults to the one built with."""
        from repro.search import MergedTopology

        if self.index is not None:
            return MergedTopology(data=data, index=self.index,
                                  metric=metric or self.metric)
        return self.shard_topology(data, metric=metric)

    def shard_topology(self, data: np.ndarray, *, metric: str | None = None):
        """The pre-merge routed serving view: the partition's (replicated)
        shards + centroids as a :class:`~repro.search.ShardTopology`.

        For merged systems this serves the same vectors through per-shard
        query routing (``repro.search.search(..., nprobe=...)``) instead of
        the global graph — ScaleGANN's bounded replication is what keeps
        routed recall high (boundary vectors live in several shards)."""
        from repro.search import ShardTopology

        return ShardTopology(
            data=data,
            shard_ids=[s.ids for s in self.shards],
            shard_graphs=self.shard_graphs,
            metric=metric or self.metric,
            centroids=self.centroids,
        )

    def search(
        self,
        data: np.ndarray,
        queries: np.ndarray,
        k: int,
        *,
        backend: str = "numpy",
        width: int = 64,
        n_entries: int = 16,
        nprobe: int | None = None,
        metric: str | None = None,
    ):
        """Serve queries on this build via :func:`repro.search.search` —
        the same call works for merged and split-only systems (``nprobe``
        routes split-topology queries; ignored on merged builds)."""
        from repro.search import search

        return search(
            self.topology(data, metric=metric), queries, k,
            backend=backend, width=width, n_entries=n_entries, nprobe=nprobe,
        )


def _build_shards(
    data: np.ndarray,
    shards: list[Shard],
    cfg: IndexConfig,
    *,
    algo: str = "cagra",
    n_workers: int = 1,
    reference: bool = False,
    max_retries: int = 2,
    retry_backoff_s: float = 0.05,
):
    build = (REFERENCE_BUILDERS if reference else BUILDERS)[algo]
    if algo == "vamana" and not reference and shards:
        # batched Vamana jits its insertion rounds: pad every shard to one
        # shared power-of-two row count so the whole build traces once,
        # not once per distinct shard size (see build_shard_index_vamana)
        pad = 1 << max(0, max(len(s.ids) for s in shards) - 1).bit_length()
        build = functools.partial(build, pad_to=pad)
    per_shard_s = [0.0] * len(shards)
    results: list = [None] * len(shards)
    attempts = [0] * len(shards)
    last_error: list[str | None] = [None] * len(shards)
    failures: dict[int, BaseException] = {}

    tr = current_tracer()

    def one(i: int):
        """One shard, with bounded retry + capped exponential backoff — a
        transient failure (OOM burst, flaky accelerator) must not abort the
        other shards' work (paper §IV: failed tasks are re-allocated, not
        fatal).  The final failure is recorded, not raised, so every shard
        gets its full retry budget before the build surfaces one error."""
        with tr.span("build.gather") if tr.enabled else NULL_SPAN:
            vecs = np.asarray(data[shards[i].ids])
        t0 = time.perf_counter()
        for attempt in range(max_retries + 1):
            attempts[i] = attempt + 1
            try:
                with tr.span("build.shard", shard=i, algo=algo,
                             n=len(shards[i].ids), attempt=attempt + 1):
                    results[i] = build(vecs, cfg)
                break
            except Exception as e:  # noqa: BLE001 — recorded + re-raised
                last_error[i] = f"{type(e).__name__}: {e}"
                if attempt == max_retries:
                    failures[i] = e
                else:
                    time.sleep(min(retry_backoff_s * (2 ** attempt), 2.0))
        per_shard_s[i] = time.perf_counter() - t0

    t0 = time.perf_counter()
    with tr.span("build.shards", track="build", n_shards=len(shards),
                 n_workers=n_workers):
        if n_workers <= 1:
            for i in range(len(shards)):
                one(i)
        else:
            with ThreadPoolExecutor(max_workers=n_workers) as pool:
                list(pool.map(one, range(len(shards))))
    wall = time.perf_counter() - t0
    if failures:
        raise ShardBuildError(
            failures, {i: attempts[i] for i in failures}
        )
    return results, per_shard_s, wall, attempts, last_error


def build_scalegann(
    data: np.ndarray,
    cfg: IndexConfig,
    *,
    algo: str = "cagra",
    n_workers: int = 1,
    selective: bool = True,
    reference: bool = False,
    max_retries: int = 2,
    retry_backoff_s: float = 0.05,
) -> BuildResult:
    """The paper's system: selective-replication partition → parallel shard
    builds → edge-union merge.  ``selective=False`` gives DiskANN's uniform
    replication (Table IV 'Original').  ``reference=True`` runs the
    seed-loop (pre-vectorization) shard-build and merge hot loops — the
    baseline ``bench_build.py`` reports speedups against.

    A shard build that raises is retried up to ``max_retries`` times with
    capped exponential backoff (``retry_backoff_s`` base) instead of
    aborting the whole build; per-shard attempt counts / last retried
    errors land in ``BuildResult.shard_attempts`` / ``.shard_errors``, and
    a shard that exhausts its budget raises :class:`ShardBuildError`
    carrying every failed shard's error.

    The whole build follows ``cfg.metric``: under ``"ip"`` the partition
    assigns the MIPS-lifted vectors, the CAGRA shard graphs rank by
    ``−x·y`` and the merge caps degree by it.  ``algo="vamana"`` is L2
    only and refuses any other metric."""
    if algo == "vamana":
        vamana.require_l2(cfg)
    tr = current_tracer()
    t0 = time.perf_counter()
    with tr.span("build.partition", track="build", n=len(data)):
        part: PartitionResult = partition(data, cfg, selective=selective)
    partition_s = time.perf_counter() - t0

    idxs, per_shard_s, wall, attempts, errors = _build_shards(
        data, part.shards, cfg, algo=algo, n_workers=n_workers,
        reference=reference, max_retries=max_retries,
        retry_backoff_s=retry_backoff_s,
    )

    t0 = time.perf_counter()
    with tr.span("build.merge", track="build", n_shards=len(part.shards)):
        merged = merge_shard_indexes(
            part.shards, idxs, len(data), cfg.degree, data=data,
            reference=reference, metric=cfg.metric,
        )
    merge_s = time.perf_counter() - t0
    return BuildResult(
        name=f"scalegann[{algo}]",
        index=merged,
        shards=part.shards,
        shard_graphs=[i.graph for i in idxs],
        partition_s=partition_s,
        build_only_s=sum(per_shard_s),
        wall_build_s=wall,
        merge_s=merge_s,
        per_shard_s=per_shard_s,
        n_distance_computations=sum(i.n_distance_computations for i in idxs),
        stats=dict(part.stats),
        metric=cfg.metric,
        centroids=part.centroids,
        shard_attempts=attempts,
        shard_errors=errors,
    )


def build_diskann(
    data: np.ndarray, cfg: IndexConfig, *, n_workers: int = 1,
    reference: bool = False,
) -> BuildResult:
    """DiskANN baseline: uniform ≥1 replication + Vamana shard builds +
    merge.

    By default the Vamana shard builds run the repo's *batched* rounds
    (same graph semantics, engine-backed searches).  Pass
    ``reference=True`` for the paper-faithful sequential CPU algorithm
    end-to-end — the paper-table benchmarks that *mean* "CPU DiskANN"
    (tables I/II/V) pin it, so their recorded claims keep measuring the
    contrast the paper measures."""
    res = build_scalegann(
        data, cfg, algo="vamana", n_workers=n_workers, selective=False,
        reference=reference,
    )
    return dataclasses.replace(res, name="diskann")


def _split_partition(
    data: np.ndarray, cfg: IndexConfig, *, kmeans: bool
) -> tuple[list[Shard], np.ndarray, float]:
    """Replication-free split: k-means shards (Extended CAGRA) or contiguous
    blocks (GGNN's naive split).  Either way the shards get routing
    centroids — kmeans centroids, or per-shard means for the naive split —
    so serving can prune which shards a query visits."""
    t0 = time.perf_counter()
    n = len(data)
    if kmeans:
        part = partition(
            data,
            dataclasses.replace(cfg, omega=1),  # originals only
            selective=True,
        )
        shards = part.shards
        centroids = part.centroids
    else:
        per = -(-n // cfg.n_clusters)
        shards = [
            Shard(
                ids=np.arange(s, min(s + per, n), dtype=np.int64),
                is_replica=np.zeros(min(per, n - s), bool),
            )
            for s in range(0, n, per)
        ]
        centroids = np.stack([
            np.asarray(data[s.ids], np.float32).mean(axis=0) for s in shards
        ])
    return shards, centroids, time.perf_counter() - t0


def build_split_only(
    data: np.ndarray,
    cfg: IndexConfig,
    *,
    name: str,
    kmeans_split: bool,
    n_workers: int = 1,
) -> BuildResult:
    """Extended CAGRA (kmeans_split=True) / GGNN (False): no replication, no
    merge; queries search the shards directly (repro.search ShardTopology),
    routed by the carried centroids when ``nprobe`` is set."""
    shards, centroids, partition_s = _split_partition(
        data, cfg, kmeans=kmeans_split
    )
    idxs, per_shard_s, wall, attempts, errors = _build_shards(
        data, shards, cfg, algo="cagra", n_workers=n_workers
    )
    return BuildResult(
        name=name,
        index=None,
        shards=shards,
        shard_graphs=[i.graph for i in idxs],
        partition_s=partition_s,
        build_only_s=sum(per_shard_s),
        wall_build_s=wall,
        merge_s=0.0,
        per_shard_s=per_shard_s,
        n_distance_computations=sum(i.n_distance_computations for i in idxs),
        stats={"n": len(data), "replica_proportion": 0.0},
        metric=cfg.metric,
        centroids=centroids,
        shard_attempts=attempts,
        shard_errors=errors,
    )


def build_extended_cagra(data, cfg, *, n_workers: int = 1) -> BuildResult:
    return build_split_only(
        data, cfg, name="extended_cagra", kmeans_split=True,
        n_workers=n_workers,
    )


def build_ggnn(data, cfg, *, n_workers: int = 1) -> BuildResult:
    return build_split_only(
        data, cfg, name="ggnn", kmeans_split=False, n_workers=n_workers
    )
