"""Executables JAX compiled or loaded from its cache between the window's
opening and the last answer of the window's requests (each such event can
stall the requests behind it)."""


def read(obs):
    return obs.get("compiles_in_window")
