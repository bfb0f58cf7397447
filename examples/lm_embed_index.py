"""The paper's pipeline on LM-produced vectors: train a small LM, extract
its token-embedding vectors, build a ScaleGANN index over them, and serve
nearest-neighbor queries (the embedding-retrieval use-case that motivates
vector databases).

    PYTHONPATH=src python examples/lm_embed_index.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig, get_arch, smoke_config
from repro.core.builder import build_scalegann
from repro.data.pipeline import TokenPipeline, TokenPipelineConfig
from repro.search import search
from repro.data.synthetic import exact_ground_truth, recall_at
from repro.models.model import build_model
from repro.train.optimizer import for_config
from repro.train.train_step import TrainConfig, init_train_state, make_train_step


def main():
    # 1. train a small LM briefly so embeddings carry co-occurrence signal
    cfg = dataclasses.replace(
        smoke_config(get_arch("granite_3_2b")), vocab_size=4096,
        d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
    )
    model = build_model(cfg)
    opt = for_config(cfg.optimizer)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=10, microbatch=4)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), tcfg)
    step = jax.jit(make_train_step(model, opt, tcfg))
    pipe = TokenPipeline(TokenPipelineConfig(vocab_size=cfg.vocab_size,
                                             seq_len=128, global_batch=8))
    for _ in range(60):
        state, metrics = step(state, {k: jnp.asarray(v) for k, v in
                                      pipe.next_batch().items()})
    print(f"LM trained 60 steps, loss {float(metrics['loss']):.3f}")

    # 2. the vector dataset = the LM's (tied) token embedding table
    table = np.asarray(state.params["embed"]["table"],
                       np.float32)[: cfg.vocab_size]
    print(f"embedding table: {table.shape}")

    # 3. ScaleGANN over the embeddings
    icfg = IndexConfig(n_clusters=6, degree=16, build_degree=32,
                       block_size=1024)
    res = build_scalegann(table, icfg, n_workers=2)
    print(f"index built: {res.overall_s:.2f}s, "
          f"replicas {res.stats['replica_proportion']:.1%}")

    # 4. serve: nearest tokens to perturbed embeddings
    rng = np.random.default_rng(0)
    probe_ids = rng.choice(cfg.vocab_size, 32, replace=False)
    queries = table[probe_ids] + 0.005 * rng.normal(
        size=(32, table.shape[1])
    ).astype(np.float32)
    gt = exact_ground_truth(table, queries, 10)
    ids, stats = search(res.index, queries, 10, data=table,
                        backend="jax", width=96)
    print(f"recall@10 = {recall_at(ids, gt, 10):.3f} "
          f"({stats.n_distance_computations/32:.0f} dists/query)")
    hit1 = np.mean([probe_ids[i] in ids[i] for i in range(32)])
    print(f"self-token found for {hit1:.0%} of probes")


if __name__ == "__main__":
    enable_compile_cache()
    main()
