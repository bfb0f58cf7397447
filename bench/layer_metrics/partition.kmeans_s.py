"""Seconds of the partition's k-means (span ``partition.kmeans``: sampled
Lloyd's iterations, on the MIPS-lifted vectors under ``"ip"``), per build
in the traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "partition.kmeans")
