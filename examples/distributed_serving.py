import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Distributed MIPS serving demo: items row-sharded into 8 shard-local
ip-NSW+ sub-indexes; queries fan out via shard_map, per-shard top-k merge
with one tiny all-gather; a dead shard degrades recall, not availability.

  PYTHONPATH=src python examples/distributed_serving.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import exact_topk, recall_at_k
from repro.core.distributed import build_sharded, sharded_search
from repro.data import mips_dataset, mips_queries


def main():
    n, d, b, k, shards = 16_000, 48, 64, 10, 8
    items = jnp.asarray(mips_dataset(n, d, profile="lognormal", seed=0))
    queries = jnp.asarray(mips_queries(b, d, seed=1))
    _, gt = exact_topk(queries, items, k=k)
    gt = np.asarray(gt)

    print(f"building {shards} shard-local ip-NSW+ indexes ({n//shards} items "
          f"each; scan backend = all shards in one device program)...")
    index = build_sharded(items, shards, plus=True, build_backend="scan",
                          max_degree=16, ef_construction=32, insert_batch=512)

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((shards,), ("model",))
    print(f"mesh: {mesh}")

    ids, scores, evals = sharded_search(index, queries, mesh=mesh, k=k, ef=40)
    print(f"all shards up:   recall@10 = {recall_at_k(np.asarray(ids), gt):.3f}  "
          f"(total evals/query {float(np.mean(np.asarray(evals))):.0f})")

    # kill shard 3: serving continues, recall degrades gracefully
    mask = np.ones(shards, bool)
    mask[3] = False
    ids_dg, _, _ = sharded_search(index, queries, mesh=mesh, k=k, ef=40,
                                  shard_mask=jnp.asarray(mask))
    print(f"shard 3 down:    recall@10 = {recall_at_k(np.asarray(ids_dg), gt):.3f}  "
          f"(availability preserved; launcher rebuilds the shard from its item partition)")


if __name__ == "__main__":
    main()
