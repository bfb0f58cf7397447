"""The readers of the merge's stage metrics (``merge.distances_s``,
``merge.cap_s``): span seconds per build in the traced window, and nothing
where the trace holds no such span."""

import pytest

from bench import common

SPANS = {"merge.edges": (2, 0.9), "merge.sort": (2, 1.8),
         "merge.distances": (2, 21.0), "merge.cap": (2, 2.8)}
BUILDS = [{"merge_s": 13.2}, {"merge_s": 13.3}]


@pytest.mark.parametrize("metric,want", [("merge.distances_s", 10.5),
                                         ("merge.cap_s", 1.4)])
def test_merge_stage_reader(metric, want):
    read = common.layer_reader(metric).read
    assert read({"spans": SPANS, "builds": BUILDS}) == pytest.approx(want)
    assert read({}) is None
    # an untraced window, a window without builds, a trace without the span
    assert read({"spans": None, "builds": BUILDS}) is None
    assert read({"spans": SPANS, "builds": []}) is None
    assert read({"spans": {"cagra.knn": (32, 3.0)}, "builds": BUILDS}) is None
