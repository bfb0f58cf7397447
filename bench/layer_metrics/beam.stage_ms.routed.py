"""Milliseconds of beam staging (span ``beam.stage``: entries, query codes
and the host-to-device uploads of each probed shard's codes and graph) per
engine call in the traced part of the window, summed over probed shards."""

from bench.spans import per_engine_call_ms


def read(obs):
    return per_engine_call_ms(obs, "beam.stage")
