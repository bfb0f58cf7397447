"""Seconds of the shard builds' detour counting (span ``cagra.detour``:
the host gathers of each block's neighbour vectors, their uploads,
``_detour_counts`` and the reads back), over every shard, per build in the
traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "cagra.detour")
