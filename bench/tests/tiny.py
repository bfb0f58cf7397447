"""The cells shrunk to a size the CPU test run holds."""

# Each cell shrunk to a size the CPU test run holds: same drivers, same
# program path, smaller data, index and traffic.
TINY = {
    "config": {"n": 1500, "assumed": {"query_pool": 64},
               "index": {"n_clusters": 2, "degree": 16, "build_degree": 32},
               # a graph this small reaches a lower recall than the
               # configuration's target
               "checks": {"recall_at_10_min": 0.8}},
    "params": {"rate_qps": 40, "warm_seconds": 0.3, "clients": 4,
               "queries": 64, "trace_seconds": 0.3,
               "search": {"width": 64, "n_entries": 32, "chunk": 32},
               # the routed path's recall at this size needs the shards'
               # beams seeded nearly everywhere
               "server": {"width": 128, "n_entries": 128, "max_batch": 8}},
}
CELLS = ("deep-f32.build", "bigann-u8.routed.closed",
         "deep-f32.merged.closed")

# The tiny merged cell as an inner-product deployment with
# out-of-distribution queries: the base, index and traffic of TINY, judged
# and served under "ip", queries drawn off the base's clusters.
OOD = {"source": "ood", "n_centres": 24, "shift": 0.5, "spread": 0.35,
       "centre_seed": 1}
IP_CELL = "deep-f32.merged.closed+ip"
IP = {"config": dict(TINY["config"], metric="ip", queries=OOD,
                     index=dict(TINY["config"]["index"], metric="ip")),
      "params": TINY["params"]}


def case(name: str) -> tuple[str, dict]:
    """``(cell, overrides)`` of a test case: a cell at its TINY size, or
    ``IP_CELL``, the merged cell under ``IP``."""
    return ("deep-f32.merged.closed", IP) if name == IP_CELL else (name, TINY)
