"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its file under
``bench/workloads/`` names its configuration (``bench/configs/``), its
traffic driver (``bench/traffic/``) and that driver's parameters.  A run:

1. refuses to start without a TPU (or with fewer chips than the cell asks);
2. sets up: generates the deployment's data and the seed's query pool,
   lets the driver build and warm everything the window will use;
   ``setup_s`` runs from process start to here;
3. measures for ``--seconds`` (with ``--trace 1`` under the profiler);
4. reads the device's peak memory, lets the driver hand over its answers and
   frees the program's state;
5. computes the exact reference and judges the answers (``bench/check.py``);
6. prints the numbers compared, each beside its limit, as the last lines of
   standard error, and the JSON result as the last line of standard output.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` they are its per-layer metrics, each read by its own file
under ``bench/layer_metrics/``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, common, data, reference  # noqa: E402
from bench import spans as spansmod  # noqa: E402
from bench import trace as tracemod  # noqa: E402


class NoChip(RuntimeError):
    pass


_COMPILED: list = []  # names of the programs compiled, in order
_LISTENING = [False]


def _listen_compiles() -> None:
    """Record every executable JAX compiles or loads from its persistent
    cache (one ``backend_compile_duration`` event each)."""
    if _LISTENING[0]:
        return
    from jax import monitoring

    def on_duration(event: str, _secs: float, fun_name: str = "?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILED.append(fun_name)

    monitoring.register_event_duration_secs_listener(on_duration)
    _LISTENING[0] = True


@dataclasses.dataclass
class Context:
    """What a driver is given: the configuration, its parameters and the
    generated inputs."""

    config: dict
    params: dict
    dep: data.Deployment
    pool: np.ndarray  # [P, D] queries in the deployment's dtype
    pool_f32: np.ndarray  # the same queries as float32, as requests carry them
    seed: int
    k: int
    persistent: bool = True  # keep compiled programs and built indexes on disk

    @staticmethod
    def compiles() -> int:
        return len(_COMPILED)

    @staticmethod
    def compiled_since(n: int) -> list:
        return _COMPILED[n:]


class _NullProfiler:
    active = False
    summary = None
    spans = None
    window_s = None

    def start(self):
        pass

    def stop(self):
        pass


class _Profiler:
    """The JAX profiler, without the Python tracer (it would slow the host
    path it measures), and the program's own spans in its trace: while it
    runs, a ``repro.telemetry.Tracer(sink="profiler")`` is the process's
    tracer, so every program span is a host event of the trace
    (``bench/spans.py``)."""

    active = True

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.summary = None
        self.spans = None
        self.window_s = None
        self._ann = None
        self._t0 = None
        self._prev_tracer = None

    def start(self):
        import jax
        from repro.telemetry import Tracer, set_tracer

        self._prev_tracer = set_tracer(Tracer(sink="profiler"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN)
        self._ann.__enter__()
        self._t0 = time.monotonic()

    def stop(self):
        import jax
        from repro.telemetry import set_tracer

        if self._ann is None:
            return
        self.window_s = time.monotonic() - self._t0
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        set_tracer(self._prev_tracer)

    def reduce(self):
        ev = tracemod.load_events(tracemod.xplane_file(self.log_dir))
        self.summary = tracemod.reduce_events(ev)
        self.spans = spansmod.reduce(ev)
        return self.summary


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, v in over.items():
        out[key] = (_merge(out[key], v) if isinstance(v, dict)
                    and isinstance(out.get(key), dict) else v)
    return out


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, persistent_cache: bool = True,
             overrides: dict | None = None,
             t_start: float | None = None) -> dict:
    """One run of cell ``name``; returns the result object.

    ``overrides`` (``{"config": {...}, "params": {...}}``, and ``"driver"``
    to drive the cell with another traffic driver) shrink a cell for the CPU
    tests; ``require_tpu=False`` lets them run without a chip and
    ``persistent_cache=False`` keeps them off the on-disk caches.
    """
    import jax

    t_start = T_START if t_start is None else t_start
    bench = common.benchmark()
    entry = common.cell_entry(bench, name)
    wl = common.workload(name)
    if (wl["config"], wl["traffic"]) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"{name}: workload file disagrees with "
                         "BENCHMARK.json on config or traffic")
    overrides = overrides or {}
    cfg = _merge(common.config(wl["config"]), overrides.get("config", {}))
    params = _merge(wl["params"], overrides.get("params", {}))
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < entry["chips"]):
        raise NoChip(f"{name} needs {entry['chips']} TPU chip(s); JAX "
                     f"reports {len(devices)} {devices[0].platform} device(s)")

    if persistent_cache:
        from repro.common.compile_cache import enable_compile_cache

        enable_compile_cache()
        # every program, however quick to compile: a second run compiles none
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _listen_compiles()

    dep = data.deployment(cfg)
    pool = data.queries(dep, cfg["assumed"]["query_pool"], seed,
                        cfg.get("queries"))
    ctx = Context(config=cfg, params=params, dep=dep, pool=pool,
                  pool_f32=pool.astype("float32"), seed=seed, k=cfg["k"],
                  persistent=persistent_cache)
    drv = common.driver(overrides.get("driver", wl["driver"])).Driver(ctx)
    drv.setup()
    setup_s = time.monotonic() - t_start

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        prof = _Profiler(tdir) if trace else _NullProfiler()
        win = drv.window(seconds, prof)
        if win["obs"].get("compiled_in_window"):
            print("compiled in the window:", win["obs"]["compiled_in_window"],
                  file=sys.stderr)
        if "longest_pause_ms" in win["obs"]:
            print("longest pause between answers in the window: "
                  f"{win['obs']['longest_pause_ms']!r} ms", file=sys.stderr)
        device = _device_info(devices)
        rows, ids, n_unanswered = drv.answers()
        graphs = getattr(drv, "first_neighbours", lambda: None)()
        drv.close()
        del drv
        gc.collect()
        if trace:
            prof.reduce()
    win["obs"]["trace"] = prof.summary
    win["obs"]["spans"] = prof.spans

    metric = cfg["metric"]
    _, gt = reference.exact_knn(dep.data, pool, ctx.k, metric=metric)
    checks, recall = check.compare(dep.data, pool, rows, ids, gt,
                                   n_unanswered, cfg["checks"], ctx.k,
                                   metric)
    if graphs is not None:
        checks["nn_gap"] = {"value": check.nn_gap(dep.data, graphs, metric),
                            "limit": cfg["checks"]["nn_gap_max"]}
    result = {
        "correct": check.passed(checks),
        "attempted": win["attempted"],
        "failed": win["failed"],
    }
    if trace:
        metrics = {}
        obs = dict(win["obs"], peaks=_peaks(device["kind"]))
        for m in common.cell_metrics(bench, name, "per_layer"):
            v = common.layer_reader(m["name"]).read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(win["metrics"], setup_s=setup_s, recall_at_10=recall)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in common.cell_metrics(bench, name, "end_to_end")
        }
    result["metrics"] = metrics
    result["device"] = device
    s = prof.summary
    if s is not None and s.n_devices:
        device["busy_s"] = s.busy_s
        device["window_s"] = prof.window_s
        result["breakdown"] = {
            "device_ops": sorted(s.module_s.items(),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": s.idle_gaps[:10],
        }
    result["checks"] = checks
    return result


def _peaks(kind: str) -> dict | None:
    table = common.load_json(common.BENCH / "peaks.json")["devices"]
    return table.get(kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    for key, c in result["checks"].items():
        print(f"check {key}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
