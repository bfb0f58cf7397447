"""Seconds of the merge's edge distances (span ``merge.distances``: the
distance of every deduplicated edge by the index's metric, on the device
or in numpy, and the cap's sort keys), per build in the traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "merge.distances")
