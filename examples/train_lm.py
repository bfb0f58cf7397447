"""End-to-end LM training driver: train a ~100M-param model for a few
hundred steps on CPU, with checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_lm.py --steps 300 --arch tinyllama_1_1b

The arch config is reduced to ~100M params (depth/width scaled, same
family); the data pipeline is the deterministic synthetic token stream with
seek-to-step, so killing and restarting this script resumes exactly.
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import get_arch
from repro.data.pipeline import TokenPipeline, TokenPipelineConfig
from repro.models.model import build_model
from repro.train import checkpoint as ckpt
from repro.train.optimizer import for_config
from repro.train.train_step import (TrainConfig, TrainState, init_train_state,
                                    make_train_step)


def hundred_m_config(arch: str):
    """Scale the assigned arch down to ~100M params (same family)."""
    cfg = get_arch(arch)
    return dataclasses.replace(
        cfg, n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=min(cfg.n_kv_heads, 4), head_dim=64, d_ff=1536,
        vocab_size=8192, remat="none",
        moe=None if cfg.moe is None else dataclasses.replace(
            cfg.moe, n_experts=8, d_ff=512
        ),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = hundred_m_config(args.arch)
    model = build_model(cfg)
    print(f"{cfg.name}: {model.n_params/1e6:.1f}M params")
    opt = for_config(cfg.optimizer)
    tcfg = TrainConfig(learning_rate=3e-4, warmup_steps=50,
                       microbatch=args.batch // 2)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), tcfg)
    step_fn = jax.jit(make_train_step(model, opt, tcfg))

    pipe = TokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch,
    ))

    # fault tolerance: resume from the newest checkpoint if one exists
    resumed = ckpt.restore_latest(args.ckpt_dir, state.params)
    if resumed:
        step0, params, _ = resumed
        state = TrainState(params=params, opt_state=opt.init(params),
                           step=jnp.int32(step0), error_state=None)
        pipe.seek(step0)
        print(f"resumed from step {step0}")

    t0 = time.perf_counter()
    while int(state.step) < args.steps:
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        state, metrics = step_fn(state, batch)
        s = int(state.step)
        if s % 20 == 0 or s == 1:
            tok_s = s * args.batch * args.seq / (time.perf_counter() - t0)
            print(f"step {s:4d}  loss {float(metrics['loss']):.4f}  "
                  f"grad-norm {float(metrics['grad_norm']):.2f}  "
                  f"{tok_s:,.0f} tok/s")
        if s % args.ckpt_every == 0:
            path = ckpt.save(args.ckpt_dir, s, state.params,
                             metadata={"arch": cfg.name})
            print(f"checkpoint → {path}")
    print(f"done: final loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
