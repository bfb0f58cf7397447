"""Device busy time per engine call in the traced part of the window, in
ms: the profiler's device operations over the engine calls made while it
ran.  Its gap to ``driver.engine_ms.merged`` is the host's share of a
call."""


def read(obs):
    s = obs.get("trace")
    n = obs.get("engine_calls_traced")
    if s is None or not s.n_devices or not n:
        return None
    return 1e3 * s.busy_s / n
