"""Share of the traced part of the window in which no operation ran on the
device, in %."""


def read(obs):
    s = obs.get("trace")
    if s is None or not s.n_devices or not obs.get("server"):
        return None
    return 100.0 * (1.0 - s.busy_s / ((s.window_ns[1] - s.window_ns[0]) * 1e-9))
