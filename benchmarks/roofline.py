"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads ``dryrun_results.json`` (produced by ``repro.launch.dryrun``) and
derives, per (arch × shape × mesh) cell:

    compute_s    = HLO_FLOPs_per_device   / PEAK_FLOPS        (197 TF bf16)
    memory_s     = HLO_bytes_per_device   / HBM_BW            (819 GB/s)
    collective_s = wire_bytes_per_device  / ICI_BW            (~50 GB/s/link)

All three inputs are *per-device* (the lowered module is the SPMD per-device
program) and loop-aware (launch/hlo_cost.py).  Wire bytes apply ring-model
factors: all-reduce ×2 (reduce-scatter + all-gather phases), others ×1.

Also reported per cell:
    MODEL_FLOPS         = 6·N_active·D (train) / 2·N_active·D (prefill)
                          / 2·N_active·B (decode), per device,
    model/HLO ratio     — how much compiled compute is "useful"
                          (catches remat / replicated-compute waste),
    dominant term + roofline fraction = ideal_compute_s / max(term)
                          (1.0 ⇒ the cell runs at the compute roofline).
"""

from __future__ import annotations

import json
import os

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import SHAPES, get_arch

from benchmarks.common import Rows

PEAK_FLOPS = 197e12  # TPU v5e bf16
HBM_BW = 819e9
ICI_BW = 50e9

TOKENS = {  # global tokens processed per step, by shape
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,  # one token per sequence
    "long_500k": 1,
}


def wire_bytes(coll: dict) -> float:
    """Ring-model wire traffic: all-reduce = RS+AG phases (×2 full tensor),
    the rest ≈ ×1 of the materialized tensor."""
    return (
        coll.get("all-gather", 0.0)
        + 2.0 * coll.get("all-reduce", 0.0)
        + coll.get("reduce-scatter", 0.0)
        + coll.get("all-to-all", 0.0)
        + coll.get("collective-permute", 0.0)
    )


def model_flops_per_device(cell: dict) -> float:
    n_active = cell["n_active_params"]
    mult = 6.0 if cell["kind"] == "train" else 2.0
    return mult * n_active * TOKENS[cell["shape"]] / cell["n_devices"]


def _kv_cache_bytes(arch: str, shape_id: str) -> float:
    """Analytic KV-cache/state bytes (bf16 k+v) the decode step must read."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_id]
    n_attn = sum(
        cfg.is_attn_layer(i) for i in range(cfg.n_layers)
    ) if cfg.family != "ssm" else 0
    kv = (2 * n_attn * shape.global_batch * cfg.n_kv_heads * shape.seq_len
          * cfg.resolved_head_dim * 2)
    if cfg.family == "encdec":
        kv += (2 * cfg.n_layers * shape.global_batch * cfg.n_kv_heads
               * cfg.n_audio_frames * cfg.resolved_head_dim * 2)
    if cfg.ssm is not None:
        d_inner = (cfg.ssm.expand * cfg.d_model)
        n_ssm = cfg.n_layers - n_attn
        kv += n_ssm * shape.global_batch * d_inner * cfg.ssm.d_state * 4
    if cfg.family == "ssm":
        kv += (cfg.n_layers * shape.global_batch * cfg.n_heads
               * cfg.resolved_head_dim ** 2 * 4)
    return float(kv)


def model_min_bytes_per_device(cell: dict) -> float:
    """Lower-bound HBM traffic per step: weights once (+grad/opt passes for
    train) + the decode KV cache/state read."""
    params_bytes = cell["n_params"] * 2.0  # bf16 compute copy
    if cell["kind"] == "train":
        # fwd read + bwd read + grad write + opt read/write (f32 master ≈ ×3)
        traffic = 2 * params_bytes + 3 * cell["n_params"] * 4.0
    elif cell["kind"] == "prefill":
        traffic = params_bytes
    else:  # decode
        traffic = params_bytes + _kv_cache_bytes(cell["arch"], cell["shape"])
    return traffic / cell["n_devices"]


def analyze_cell(cell: dict) -> dict:
    compute_s = cell["flops"] / PEAK_FLOPS
    memory_s = cell["bytes_accessed"] / HBM_BW
    collective_s = wire_bytes(cell["collectives"]) / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(cell)
    mb = model_min_bytes_per_device(cell)
    # the cell's *ideal* step time: whichever model-level roofline binds
    ideal_s = max(mf / PEAK_FLOPS, mb / HBM_BW)
    bound_s = max(terms.values())
    return {
        "arch": cell["arch"],
        "shape": cell["shape"],
        "mesh": cell["mesh"],
        "kind": cell["kind"],
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops_per_dev": mf,
        "model_over_hlo": mf / max(cell["flops"], 1e-9),
        "ideal_s": ideal_s,
        "roofline_fraction": ideal_s / max(bound_s, 1e-30),
        "step_s_bound": bound_s,
    }


def load(path: str = "dryrun_results.json") -> list[dict]:
    with open(path) as f:
        results = json.load(f)
    return [analyze_cell(c) for c in results if c.get("status") == "ok"]


def markdown_table(cells: list[dict], mesh: str = "single") -> str:
    rows = [c for c in cells if c["mesh"] == mesh]
    rows.sort(key=lambda c: (c["arch"], c["shape"]))
    out = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant "
        "| model/HLO | roofline frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for c in rows:
        out.append(
            f"| {c['arch']} | {c['shape']} | {c['compute_s']:.3e} "
            f"| {c['memory_s']:.3e} | {c['collective_s']:.3e} "
            f"| **{c['dominant']}** | {c['model_over_hlo']:.3f} "
            f"| {c['roofline_fraction']:.3f} |"
        )
    return "\n".join(out)


def main() -> Rows:
    rows = Rows("roofline")
    path = os.environ.get("REPRO_DRYRUN_JSON", "dryrun_results.json")
    if not os.path.exists(path):
        rows.add("status", f"missing {path} — run repro.launch.dryrun first")
        return rows
    cells = load(path)
    rows.add("n_cells", len(cells))
    worst = sorted(cells, key=lambda c: c["roofline_fraction"])[:3]
    for i, c in enumerate(worst):
        rows.add(f"worst{i}",
                 f"{c['arch']}/{c['shape']}/{c['mesh']}"
                 f" frac={c['roofline_fraction']:.4f} dom={c['dominant']}")
    most_coll = max(cells, key=lambda c: c["collective_s"]
                    / max(c["step_s_bound"], 1e-30))
    rows.add("most_collective_bound",
             f"{most_coll['arch']}/{most_coll['shape']}/{most_coll['mesh']}")
    for c in cells:
        rows.add(
            f"{c['arch']}.{c['shape']}.{c['mesh']}",
            f"dom={c['dominant']} frac={c['roofline_fraction']:.4f}",
        )
    with open("roofline_table.md", "w") as f:
        f.write("## Single-pod (16×16)\n\n")
        f.write(markdown_table(cells, "single"))
        f.write("\n\n## Multi-pod (2×16×16)\n\n")
        f.write(markdown_table(cells, "multi"))
        f.write("\n")
    rows.add("table_written", "roofline_table.md")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
