"""Milliseconds of beam staging (span ``beam.stage``: entries, queries and
the host-to-device upload of the whole store and graph) per engine call in
the traced part of the window."""

from bench.spans import per_engine_call_ms


def read(obs):
    return per_engine_call_ms(obs, "beam.stage")
