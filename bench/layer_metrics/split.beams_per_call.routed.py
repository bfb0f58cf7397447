"""Beam searches per engine call over the window (``ServerStats``: one per
probed shard, unscaled by padding)."""


def read(obs):
    s = obs.get("server")
    if not s or not s["n_batches"] or "beam_calls_per_batch" not in s:
        return None
    return s["beam_calls_per_batch"]
