"""Mean wall time of one engine call, in ms (``ServerStats``: engine time
over engine calls in the window): for the merged path, staging and upload
of the store and graph and one jitted beam."""


def read(obs):
    s = obs.get("server")
    return s["engine_time_ms_per_batch"] if s and s["n_batches"] else None
