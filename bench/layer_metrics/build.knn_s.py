"""Seconds of the shard builds' exact kNN stage (span ``cagra.knn``: the
Pallas kNN blocks, ``_drop_self`` and their host copies), over every shard,
per build in the traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "cagra.knn")
