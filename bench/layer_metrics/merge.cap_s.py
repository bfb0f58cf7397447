"""Seconds of the merge's degree cap (span ``merge.cap``: the host sorts
that rank each row's unique neighbours and the scatter of the kept ones
into the graph), per build in the traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "merge.cap")
