"""MeshGraphNet (Pfaff et al., arXiv:2010.03409) — encode-process-decode GNN.

Message passing is implemented with ``jax.ops.segment_sum`` over an
edge-index -> node scatter (JAX has no sparse SpMM path worth using here;
the segment machinery IS the system, per the assignment note).

Distribution (DESIGN.md §5): edges AND node states are sharded over ALL mesh
axes (flattened "pod" x "data" x "model").  Per processor layer, each shard
  1. all-gathers the node-state shard into a transient full [N, d] block,
  2. runs the edge MLP + local segment_sum into a full-size partial aggregate,
  3. reduce-scatters the partials back to the node owner shards,
  4. updates its node-state shard with the node MLP.
The resident node state is [N/P, d] (ZeRO-style — 2.45M-node ogb_products
would not fit replicated through 15 layers of autodiff); the transient
gather + scatter move the same bytes a psum would, so the collective term is
unchanged but the memory term drops by P.  The AG/RS pair of [N, d_hidden]
per layer is the dominant collective for the big-graph shapes — it is the
collective-bound roofline cell and a §Perf hillclimb target.

Four shape regimes share this code path:
  full-batch small/large   — edges as given
  sampled minibatch        — padded subgraph from data/sampler.py (fanout)
  batched small graphs     — disjoint union (block-diagonal edge index)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models.layers import _dense_init

ALL_AXES = ("pod", "data", "model")


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str = "meshgraphnet"
    n_layers: int = 15
    d_hidden: int = 128
    mlp_layers: int = 2       # hidden layers per MLP (paper: 2)
    d_feat: int = 128         # input node-feature dim
    d_edge: int = 4           # input edge-feature dim (>=1; synthetic if absent)
    out_dim: int = 3          # decoded per-node output (e.g. acceleration)
    aggregator: str = "sum"
    dtype: Any = jnp.float32
    remat: bool = True


# ---------------------------------------------------------------------------
# MLP + LayerNorm block (MeshGraphNet uses LN after every MLP)
# ---------------------------------------------------------------------------


def _mlp_ln_init(key, d_in, d_hidden, d_out, n_hidden, dtype, ln=True):
    dims = [d_in] + [d_hidden] * n_hidden + [d_out]
    ks = jax.random.split(key, len(dims) - 1)
    p = {
        "w": [_dense_init(ks[i], (dims[i], dims[i + 1]), dtype) for i in range(len(dims) - 1)],
        "b": [jnp.zeros((dims[i + 1],), dtype) for i in range(len(dims) - 1)],
    }
    if ln:
        p["ln_g"] = jnp.ones((d_out,), dtype)
        p["ln_b"] = jnp.zeros((d_out,), dtype)
    return p


def _mlp_ln(p, x):
    n = len(p["w"])
    for i in range(n):
        x = x @ p["w"][i] + p["b"][i]
        if i < n - 1:
            x = jax.nn.relu(x)
    if "ln_g" in p:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["ln_g"] + p["ln_b"]
    return x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_params(key, cfg: GNNConfig):
    k1, k2, k3, kl = jax.random.split(key, 4)
    h, m = cfg.d_hidden, cfg.mlp_layers
    layer_keys = jax.random.split(kl, cfg.n_layers * 2).reshape(cfg.n_layers, 2, *kl.shape)

    def proc_init(ks):
        return {
            # edge MLP input: [e, h_src, h_dst]
            "edge": _mlp_ln_init(ks[0], 3 * h, h, h, m, cfg.dtype),
            # node MLP input: [h, agg(e)]
            "node": _mlp_ln_init(ks[1], 2 * h, h, h, m, cfg.dtype),
        }

    params = {
        "node_enc": _mlp_ln_init(k1, cfg.d_feat, h, h, m, cfg.dtype),
        "edge_enc": _mlp_ln_init(k2, cfg.d_edge, h, h, m, cfg.dtype),
        "proc": jax.vmap(proc_init)(layer_keys),
        "dec": _mlp_ln_init(k3, h, h, cfg.out_dim, m, cfg.dtype, ln=False),
    }
    return params


def init(key, cfg: GNNConfig):
    return _init_params(key, cfg), specs(cfg)


def specs(cfg: GNNConfig):
    """All GNN parameters are tiny (~MB) — replicated; state/edges shard."""
    rep = lambda p: jax.tree.map(lambda _: P(), p)
    dummy = jax.eval_shape(lambda k: _init_params(k, cfg), jax.random.PRNGKey(0))
    return jax.tree.map(lambda _: P(), dummy)


def data_specs(axes=ALL_AXES):
    """Shardings for the graph tensors: everything row-sharded over every
    mesh axis (node and edge counts are padded to multiples of the device
    count by the config layer)."""
    a = tuple(axes)
    return {
        "node_feat": P(a, None),
        "edge_feat": P(a, None),
        "src": P(a),
        "dst": P(a),
        "targets": P(a, None),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _aggregate(e, dst, n_nodes, aggregator="sum"):
    seg = jnp.where(dst >= 0, dst, n_nodes)
    if aggregator == "sum":
        agg = jax.ops.segment_sum(e, seg, num_segments=n_nodes + 1)
    elif aggregator == "max":
        agg = jax.ops.segment_max(e, seg, num_segments=n_nodes + 1)
    else:
        raise ValueError(aggregator)
    return agg[:-1]


def _proc_layer_local(lp, hn, e, src, dst, aggregator):
    """One processor layer on a (possibly local) edge block; returns the new
    edge block and the PARTIAL node aggregate (caller psums + updates)."""
    safe_src = jnp.maximum(src, 0)
    safe_dst = jnp.maximum(dst, 0)
    msg_in = jnp.concatenate([e, hn[safe_src], hn[safe_dst]], axis=-1)
    e_new = e + _mlp_ln(lp["edge"], msg_in)
    e_new = jnp.where((src >= 0)[:, None], e_new, 0)
    agg = _aggregate(e_new, dst, hn.shape[0], aggregator)
    return e_new, agg


def forward(params, graph, cfg: GNNConfig, mesh: Optional[jax.sharding.Mesh] = None):
    """graph = {node_feat [N, d_feat], edge_feat [E, d_edge],
    src [E] int32, dst [E] int32 (-1 padding)} -> node outputs [N, out_dim].

    With a mesh: node_feat/edge tensors arrive row-sharded (data_specs());
    encoder/decoder MLPs are row-parallel under plain GSPMD, the message-
    passing layers run in shard_map with the gather/scatter schedule in the
    module docstring.  N and E must be divisible by the device count.
    """
    hn = _mlp_ln(params["node_enc"], graph["node_feat"].astype(cfg.dtype))
    e = _mlp_ln(params["edge_enc"], graph["edge_feat"].astype(cfg.dtype))
    src, dst = graph["src"], graph["dst"]

    use_shard_map = mesh is not None and mesh.devices.size > 1
    axes = tuple(a for a in ALL_AXES if mesh is not None and a in mesh.axis_names)

    def layer(hn, e, lp):
        if use_shard_map:
            def body(lp, hn_blk, e_blk, src_blk, dst_blk):
                hn_full = jax.lax.all_gather(hn_blk, axes, axis=0, tiled=True)
                e_new, agg = _proc_layer_local(
                    lp, hn_full, e_blk, src_blk, dst_blk, cfg.aggregator
                )
                agg_blk = jax.lax.psum_scatter(
                    agg, axes, scatter_dimension=0, tiled=True
                )
                hn_new = hn_blk + _mlp_ln(
                    lp["node"], jnp.concatenate([hn_blk, agg_blk], axis=-1)
                )
                return hn_new, e_new

            hn_new, e_new = shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    jax.tree.map(lambda _: P(), lp),
                    P(axes, None),
                    P(axes, None),
                    P(axes),
                    P(axes),
                ),
                out_specs=(P(axes, None), P(axes, None)),
                check_vma=False,
            )(lp, hn, e, src, dst)
        else:
            e_new, agg = _proc_layer_local(lp, hn, e, src, dst, cfg.aggregator)
            hn_new = hn + _mlp_ln(lp["node"], jnp.concatenate([hn, agg], axis=-1))
        return hn_new, e_new

    # scan over processor layers (edge state is threaded through the carry);
    # remat so backward recomputes the [N, d] all-gathers instead of saving
    # 15 of them (19.8 -> ~2 GiB temp on ogb_products)
    def scan_body(carry, lp):
        hn, e = carry
        hn2, e2 = layer(hn, e, lp)
        return (hn2, e2), None

    body = jax.checkpoint(scan_body) if cfg.remat else scan_body
    (hn, e), _ = jax.lax.scan(body, (hn, e), params["proc"])
    return _mlp_ln(params["dec"], hn)


def mse_loss(params, graph, cfg: GNNConfig, mesh=None):
    out = forward(params, graph, cfg, mesh)
    return jnp.mean((out - graph["targets"].astype(out.dtype)) ** 2)
