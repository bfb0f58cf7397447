"""Seconds of the partition's blockwise assignment (span
``partition.assign``: originals, replicas and the fairness statistic over
every block, lifted under ``"ip"``), per build in the traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "partition.assign")
