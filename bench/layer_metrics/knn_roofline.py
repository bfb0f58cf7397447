"""Share of its roofline that the build's exact kNN kernel reaches, in %.

The least time the chip could take for the kernel's work
(``bench/work/knn.py``: exact brute-force kNN of every shard the traced
builds report; compute bound at these sizes) over the kernel's device time
in the trace: the TPU custom calls inside ``knn_pallas`` (its one
``pallas_call``; the trace names the op after the jitted function)."""

from bench.trace import matching_seconds
from bench.work import knn

KERNEL = ("knn_pallas", "tpu_custom_call")


def read(obs):
    s = obs.get("trace")
    builds = obs.get("builds")
    if s is None or not s.n_devices or not builds:
        return None
    t = matching_seconds(s.op_s, *KERNEL)
    if t <= 0:
        return None
    if obs.get("peaks") is None:
        raise KeyError("no peaks for this device in bench/peaks.json")
    sizes = [n for b in builds for n in b["shard_sizes"]]
    ops, nbytes = knn.work(sizes, obs["dim"], obs["knn_k"])
    least, _bound = knn.least_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * least / t
