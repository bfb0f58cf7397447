"""Megabytes (1e6 B) of host arrays the beam uploads per engine call over
the window (``ServerStats``: the whole store and graph, entries and
queries)."""


def read(obs):
    s = obs.get("server")
    if not s or not s["n_batches"] or "h2d_bytes_per_batch" not in s:
        return None
    return s["h2d_bytes_per_batch"] / 1e6
