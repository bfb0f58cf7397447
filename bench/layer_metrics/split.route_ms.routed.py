"""Milliseconds of host routing (span ``split.route``: the query-centroid
tile on uint8 codes, its f32 fallback and the probe choice) per engine
call in the traced part of the window."""

from bench.spans import per_engine_call_ms


def read(obs):
    return per_engine_call_ms(obs, "split.route")
