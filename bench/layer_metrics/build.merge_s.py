"""Host merge seconds (``core/merge.py``), mean per build in the window,
from ``BuildResult.merge_s``."""


def read(obs):
    b = obs.get("builds")
    return sum(x["merge_s"] for x in b) / len(b) if b else None
