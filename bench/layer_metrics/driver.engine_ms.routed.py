"""Mean wall time of one engine call, in ms (``ServerStats``: engine time
over engine calls in the window): for the routed path, host routing, one
jitted beam per probed shard, the pool merge and the exact re-rank."""


def read(obs):
    s = obs.get("server")
    return s["engine_time_ms_per_batch"] if s and s["n_batches"] else None
