"""Shard-build seconds (``core/cagra.py`` over every shard), mean per build
in the window, from ``BuildResult.wall_build_s``; the phase ends in host
arrays, so its clock covers the device work."""


def read(obs):
    b = obs.get("builds")
    return sum(x["shards_s"] for x in b) / len(b) if b else None
