"""Batched LM serving: prefill + slot-based decode over the serving engine.

    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6_1_6b
"""

import argparse

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import get_arch, smoke_config
from repro.models.model import build_model
from repro.serve.engine import Request, ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    cfg = smoke_config(get_arch(args.arch))
    model = build_model(cfg, max_seq_len=128)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         ServeConfig(max_len=128, n_slots=4,
                                     temperature=0.8))
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab_size, 8 + i,
                                    dtype=np.int32),
                max_new_tokens=args.max_new)
        for i in range(args.requests)
    ]
    engine.generate(reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt[{len(r.prompt)}] → "
              f"{len(r.output)} tokens: {r.output[:12]}…")
    assert all(r.done for r in reqs)
    print(f"served {len(reqs)} requests on {cfg.name} "
          f"({cfg.family}) with slot batching")


if __name__ == "__main__":
    enable_compile_cache()
    main()
