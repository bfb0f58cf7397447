"""Mean real (not padding) requests per engine call (``ServerStats``)."""


def read(obs):
    s = obs.get("server")
    return s["batch_occupancy"]["mean"] if s and s["n_batches"] else None
