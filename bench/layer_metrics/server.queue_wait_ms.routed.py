"""Mean wait from a request's submission to its batch's flush, in ms
(``ServerStats`` queue-wait histogram over the window)."""


def read(obs):
    s = obs.get("server")
    return s["queue_wait_ms"]["mean"] if s and s["n_completed"] else None
