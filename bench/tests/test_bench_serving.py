"""What the serving drivers set up: the routed shapes they warm, the index
they keep between runs, and the open-loop generator."""

import copy

import numpy as np

from bench import run, serving
from bench.tests.tiny import TINY
from bench.traffic import open_poisson


def test_group_sizes_follow_the_programs_padding():
    from repro.search.types import _bucket_size
    from repro.serving.server import bucket_batch_size

    sizes = serving.group_sizes(64)
    assert sizes == [1, 2, 4, 8, 10, 12, 14, 16, 20, 24, 28, 32, 40, 48, 56,
                     64]
    for m in range(1, 65):
        nq = bucket_batch_size(m, 64)
        assert nq in sizes
        assert all(min(_bucket_size(g), nq) in sizes for g in range(1, nq))


def test_index_cache_builds_once_per_program_and_config(tmp_path,
                                                        monkeypatch):
    import repro.core.builder as builder

    monkeypatch.setattr(serving, "INDEX_CACHE", tmp_path)
    built = []
    real = builder.build_scalegann

    def counting(*a, **kw):
        built.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(builder, "build_scalegann", counting)
    cfg = {"index": {"n_clusters": 2, "degree": 8, "build_degree": 16},
           "n": 400}
    x = np.random.default_rng(0).normal(size=(400, 8)).astype(np.float32)
    a = serving.built_index(x, cfg, cache=True)
    b = serving.built_index(x, cfg, cache=True)
    assert len(built) == 1 and len(list(tmp_path.iterdir())) == 1
    for ga, gb in zip(a.shard_graphs, b.shard_graphs):
        assert (ga == gb).all()
    serving.built_index(x, dict(cfg, n=401), cache=True)
    serving.built_index(x, cfg, cache=False)
    assert len(built) == 3 and len(list(tmp_path.iterdir())) == 2


def test_open_poisson_offers_the_same_arrivals_to_every_seed():
    gaps = {}
    for seed in (1, 2):
        offs, rows = open_poisson.schedule(50.0, 2.0, 10,
                                           np.random.default_rng(seed))
        assert len(offs) == len(rows) == 100 and offs[0] == 0.0
        gaps[seed] = np.diff(offs)
    assert not np.allclose(gaps[1], gaps[2])  # another order...
    for seed in (1, 2):  # ...of one set of gaps
        other = gaps[3 - seed]
        assert np.isclose(gaps[seed][:, None], other[None]).any(1).sum() >= 98


def test_open_poisson_drives_a_routed_cell():
    ov = copy.deepcopy(TINY)
    ov["driver"] = "open_poisson"
    r = run.run_cell("bigann-u8.routed.closed", 77, 1.0, False,
                     require_tpu=False, persistent_cache=False, overrides=ov)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] == ov["params"]["rate_qps"]
