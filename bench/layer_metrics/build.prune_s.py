"""Seconds of the shard builds' host pruning (span ``cagra.prune``: the
detour-rank argsort, the reverse-edge fill, the row dedup and refill), over
every shard, per build in the traced window."""

from bench.spans import per_build_s


def read(obs):
    return per_build_s(obs, "cagra.prune")
