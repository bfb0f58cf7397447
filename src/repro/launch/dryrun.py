import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above run BEFORE any other import — jax locks the device count
at first init, and the production meshes need 512 placeholder devices.

For each cell this script:
  1. builds the model + abstract inputs (ShapeDtypeStruct only — nothing is
     allocated, which is how a 1T-param train step lowers on a 1-CPU host);
  2. resolves parameter/optimizer/batch/cache shardings from the logical-
     axis rules;
  3. ``jax.jit(step).lower(...)`` then ``.compile()`` against the 16×16
     single-pod mesh and the (2,16,16) multi-pod mesh;
  4. records ``memory_analysis()``, ``cost_analysis()`` and collective bytes
     parsed from the optimized HLO → ``dryrun_results.json`` (consumed by
     benchmarks/roofline.py and EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--mesh single|multi|both] [--out PATH] [--quiet]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.common import params as par  # noqa: E402
from repro.configs.base import (SHAPES, ModelConfig, cells,  # noqa: E402
                                get_arch)
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import hlo_cost  # noqa: E402
from repro.launch import specs as lspecs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.train import train_step as ts  # noqa: E402
from repro.train.optimizer import for_config  # noqa: E402


def _abstract_state(model, opt, tcfg):
    sspec = ts.state_spec(model, opt, tcfg)
    return par.abstract_params(sspec)


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    if v in ("true", "True"):
        v = True
    elif v in ("false", "False"):
        v = False
    else:
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
    return k, v


def run_cell(arch_id: str, shape_id: str, *, multi_pod: bool,
             quiet: bool = False, rules=None, extra: dict | None = None,
             overrides: dict | None = None):
    import dataclasses

    cfg: ModelConfig = get_arch(arch_id)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_id]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules or shd.DEFAULT_RULES
    cell = lspecs.make_cell(cfg, shape)
    model = cell.model
    t0 = time.perf_counter()

    if cell.kind == "train":
        opt = for_config(cfg.optimizer)
        tcfg = ts.TrainConfig(microbatch=shape.resolved_microbatch,
                              **(extra or {}))
        state_abs = _abstract_state(model, opt, tcfg)
        state_sh = shd.param_shardings(ts.state_spec(model, opt, tcfg),
                                       mesh, rules)
        batch_sh = lspecs.batch_shardings(cell.batch_specs, mesh, rules)
        step = ts.make_train_step(model, opt, tcfg)

        def wrapped(state, batch):
            with shd.use_mesh_rules(mesh, rules):
                return step(state, batch)

        with mesh:
            lowered = jax.jit(
                wrapped,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_abs, cell.batch_specs)
    elif cell.kind == "prefill":
        params_abs = model.abstract_params(jnp.bfloat16)
        params_sh = shd.param_shardings(model.spec, mesh, rules)
        batch_sh = lspecs.batch_shardings(cell.batch_specs, mesh, rules)
        cache_sh_out = None  # let GSPMD place prefill cache output

        def prefill_step(params, batch):
            with shd.use_mesh_rules(mesh, rules):
                return model.prefill_fn(params, batch, shape.seq_len)

        with mesh:
            lowered = jax.jit(
                prefill_step,
                in_shardings=(params_sh, batch_sh),
            ).lower(params_abs, cell.batch_specs)
    else:  # decode
        params_abs = model.abstract_params(jnp.bfloat16)
        params_sh = shd.param_shardings(model.spec, mesh, rules)
        cache_sh = lspecs.cache_shardings(cell.cache_specs, mesh, rules)
        tok_spec, pos_spec = cell.token_specs
        tok_sh = shd.batch_sharding(mesh, tok_spec.shape, rules)

        def serve_step(params, cache, tokens, pos):
            with shd.use_mesh_rules(mesh, rules):
                return model.decode_fn(params, cache, tokens, pos)

        with mesh:
            lowered = jax.jit(
                serve_step,
                in_shardings=(params_sh, cache_sh, tok_sh, None),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            ).lower(params_abs, cell.cache_specs, tok_spec, pos_spec)

    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    xla_cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(
                mem, "peak_memory_in_bytes",
                getattr(mem, "temp_size_in_bytes", None),
            ),
        }
    except Exception as e:  # backend-dependent
        mem_info = {"error": str(e)}
    t0 = time.perf_counter()
    cost = hlo_cost.analyze(compiled.as_text())  # loop-aware, per-device
    analyze_s = time.perf_counter() - t0

    n_dev = mesh.devices.size
    result = {
        "arch": arch_id,
        "shape": shape_id,
        "mesh": "multi" if multi_pod else "single",
        "kind": cell.kind,
        "n_devices": n_dev,
        "n_params": model.n_params,
        "n_active_params": model.n_active_params(),
        # loop-aware per-device costs (see launch/hlo_cost.py)
        "flops": cost["flops"],
        "bytes_accessed": cost["bytes"],
        "collectives": cost["collective_bytes"],
        "n_collective_ops": cost["n_collectives"],
        # XLA's own (loop bodies counted once — kept for reference)
        "xla_flops": xla_cost.get("flops"),
        "xla_bytes": xla_cost.get("bytes accessed"),
        "memory": mem_info,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "analyze_s": round(analyze_s, 2),
        "status": "ok",
    }
    if not quiet:
        print(
            f"[dryrun] {arch_id:20s} {shape_id:12s} "
            f"{'multi' if multi_pod else 'single':6s} "
            f"flops/dev={result['flops']:.3e} "
            f"coll/dev={cost['total_collective_bytes']:.3e}B "
            f"lower={lower_s:.1f}s compile={compile_s:.1f}s"
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--append", action="store_true",
                    help="merge into an existing results file")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    metavar="KEY=VALUE",
                    help="ModelConfig overrides for §Perf variants, e.g. "
                         "--set attn_impl=fa2 --set attn_seq_shard=true")
    ap.add_argument("--tag", default=None,
                    help="variant tag recorded in the result rows")
    args = ap.parse_args()
    overrides = dict(_parse_override(kv) for kv in args.overrides)

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]
    results = []
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("tag")) for r in results
            if r.get("status") == "ok"}

    for arch_id, shape_id, runnable, reason in cells():
        if args.arch and arch_id != args.arch:
            continue
        if args.shape and shape_id != args.shape:
            continue
        if not runnable:
            results.append(
                {"arch": arch_id, "shape": shape_id, "status": "skipped",
                 "reason": reason}
            )
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            if not args.quiet:
                print(f"[dryrun] {arch_id:20s} {shape_id:12s} SKIP ({reason[:60]}…)")
            continue
        for multi in meshes:
            key = (arch_id, shape_id, "multi" if multi else "single",
                   args.tag)
            if key in done:
                continue
            try:
                res = run_cell(arch_id, shape_id, multi_pod=multi,
                               quiet=args.quiet, overrides=overrides)
                if args.tag:
                    res["tag"] = args.tag
                results.append(res)
            except Exception as e:
                traceback.print_exc()
                results.append(
                    {"arch": arch_id, "shape": shape_id,
                     "mesh": "multi" if multi else "single",
                     "status": "error", "error": f"{type(e).__name__}: {e}"}
                )
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    n_ok = sum(r.get("status") == "ok" for r in results)
    n_err = sum(r.get("status") == "error" for r in results)
    n_skip = sum(r.get("status") == "skipped" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_err} errors, {n_skip} skipped "
          f"→ {args.out}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
