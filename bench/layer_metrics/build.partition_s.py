"""Partition seconds (``core/partition.py``), mean per build in the window,
from ``BuildResult.partition_s``."""


def read(obs):
    b = obs.get("builds")
    return sum(x["partition_s"] for x in b) / len(b) if b else None
