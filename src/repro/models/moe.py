"""Mixture-of-Experts layer (GShard top-k routing with capacity) — TPU-native
expert parallelism.

Dispatch strategy (DESIGN.md §5): tokens are sharded over the batch axes and
*replicated* over the ``model`` axis; experts are sharded over ``model``.
Inside a shard_map over the full mesh, every model shard
  1. routes its (replicated) local tokens,
  2. *selects* the tokens destined to its OWN E/P experts (sort-based ragged
     dispatch — argsort by expert id + rank-in-segment, capacity-dropped),
  3. runs its local expert FFNs,
  4. scatter-adds weighted outputs back to token positions, and
  5. psum's the partial outputs over ``model``.

No all-to-all of token activations is needed because tokens are already
replicated across the expert axis; the only collective is one [T_local, d]
all-reduce per MoE layer (same order as a Megatron TP MLP), which the
roofline analysis accounts under the collective term.

The identical dispatch body runs unsharded (expert_lo=0, all experts, no
psum) for single-device smoke tests and as the oracle for the sharded path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models.layers import MODEL_AXIS, _dense_init

NEG_INF = float("-inf")


def moe_specs():
    # experts over "model" (expert parallelism), d_model FSDP over "data";
    # the dispatch shard_map's in_specs gather the "data" dim just-in-time.
    return {
        "router": P("data", None),
        "w_gate": P(MODEL_AXIS, "data", None),
        "w_in": P(MODEL_AXIS, "data", None),
        "w_out": P(MODEL_AXIS, None, "data"),
    }


def moe_init(key, d_model, d_ff, n_experts, dtype=jnp.bfloat16, expert_split: int = 1):
    """``expert_split`` > 1 stores each expert as ``split`` column-shards of
    its FFN ([E*split, d, f/split]) so that E*split divides the model-axis
    size even when E < mesh["model"] (grok-1: 8 experts x split 2 = 16).
    Splitting is EXACT for SwiGLU: the ffn dim is elementwise between the
    gate/in matmuls and the out matmul, so summing the halves' outputs
    reproduces the full expert."""
    ks = jax.random.split(key, 4)
    e_eff = n_experts * expert_split
    f_eff = d_ff // expert_split
    params = {
        "router": _dense_init(ks[0], (d_model, n_experts), jnp.float32),
        "w_gate": _dense_init(ks[1], (e_eff, d_model, f_eff), dtype),
        "w_in": _dense_init(ks[2], (e_eff, d_model, f_eff), dtype),
        "w_out": _dense_init(ks[3], (e_eff, f_eff, d_model), dtype),
    }
    return params, moe_specs()


def _dispatch_local(
    x2d: jax.Array,        # [T, d] local tokens
    router: jax.Array,     # [d, E]
    w_gate: jax.Array,     # [El, d, f'] — this shard's (split-)experts
    w_in: jax.Array,
    w_out: jax.Array,
    expert_lo: jax.Array,  # [] int32 — first (split-)expert id on this shard
    *,
    top_k: int,
    capacity: int,
    split: int = 1,
):
    """Route + select + compute + combine for one shard's expert slice.
    Returns (partial_out [T, d], aux_loss_partial)."""
    t, d = x2d.shape
    e = router.shape[1]
    el = w_gate.shape[0]

    logits = (x2d.astype(jnp.float32) @ router).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)               # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # Switch-style load-balance aux loss (computed on full router probs,
    # before any expert splitting).
    me = probs.mean(axis=0)                                          # [E]
    ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
        jnp.ones((t * top_k,), jnp.float32)
    ) / (t * top_k)
    aux = e * jnp.sum(me * ce)

    if split > 1:
        # route to every column-shard of the chosen expert (exact for
        # SwiGLU; see moe_init)
        gate_idx = (
            gate_idx[..., None] * split + jnp.arange(split, dtype=gate_idx.dtype)
        ).reshape(t, top_k * split)
        gate_vals = jnp.repeat(gate_vals, split, axis=-1)
        top_k = top_k * split

    # ---- sort-based ragged dispatch over the flat (token, choice) list ----
    flat_expert = gate_idx.reshape(-1)                               # [T*k]
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    s_expert = flat_expert[order]
    s_token = flat_token[order]
    s_gate = flat_gate[order]

    # rank within expert segment
    idx = jnp.arange(t * top_k, dtype=jnp.int32)
    seg_first = jnp.concatenate(
        [jnp.ones((1,), bool), s_expert[1:] != s_expert[:-1]]
    )
    seg_start = jax.lax.cummax(jnp.where(seg_first, idx, 0))
    rank = idx - seg_start

    local_e = s_expert - expert_lo
    keep = (rank < capacity) & (local_e >= 0) & (local_e < el)
    slot = jnp.where(keep, local_e * capacity + rank, el * capacity)  # spill row

    # §Perf (MoE dispatch v2): scatter token INDICES + gates into the
    # capacity buffer, then gather/scatter-add [El, capacity, d] tensors.
    # The naive formulation materializes [T*top_k, d] (8.6 GB fp32 per
    # qwen3 layer); this one touches only capacity-sized buffers.
    buf_tok = jnp.full((el * capacity + 1,), t, jnp.int32)
    buf_tok = buf_tok.at[slot].set(jnp.where(keep, s_token, t))
    buf_gate = jnp.zeros((el * capacity + 1,), jnp.float32)
    buf_gate = buf_gate.at[slot].set(jnp.where(keep, s_gate, 0.0))
    buf_tok = buf_tok[: el * capacity]
    buf_gate = buf_gate[: el * capacity]

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    buf = x_pad[buf_tok].reshape(el, capacity, d)

    # expert FFN (SwiGLU), batched over local experts
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_in)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, w_out).reshape(el * capacity, d)

    # combine: gate-weight in place, one scatter-add back to token rows
    y = y * buf_gate[:, None].astype(y.dtype)
    out = jnp.zeros((t + 1, d), x2d.dtype).at[buf_tok].add(y)[:t]
    return out, aux


def moe_apply(
    params,
    x: jax.Array,  # [B, S, d]
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    expert_split: int = 1,
    mesh: Optional[jax.sharding.Mesh] = None,
):
    """Returns (out [B, S, d], aux_loss scalar)."""
    b, s, d = x.shape
    e_eff = n_experts * expert_split
    if mesh is not None and MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1:
        p = mesh.shape[MODEL_AXIS]
        el = e_eff // p
        batch_axes = tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape[a]
        t_local = (b // dp) * s
        capacity = max(8, int(t_local * top_k / n_experts * capacity_factor))

        def body(router, w_gate, w_in, w_out, xb):
            lo = (jax.lax.axis_index(MODEL_AXIS) * el).astype(jnp.int32)
            x2d = xb.reshape(-1, d)
            out, aux = _dispatch_local(
                x2d, router, w_gate, w_in, w_out, lo,
                top_k=top_k, capacity=capacity, split=expert_split,
            )
            out = jax.lax.psum(out, MODEL_AXIS)
            aux = jax.lax.pmean(aux, MODEL_AXIS)
            aux = jax.lax.pmean(aux, batch_axes)
            return out.reshape(xb.shape), aux

        out, aux = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(None, None),                # router replicated over manual
                P(MODEL_AXIS, None, None),    # experts sharded (the "data"
                P(MODEL_AXIS, None, None),    #   storage dim is gathered
                P(MODEL_AXIS, None, None),    #   just-in-time = FSDP)
                P(batch_axes, None, None),    # tokens batch-sharded
            ),
            out_specs=(P(batch_axes, None, None), P()),
            check_vma=False,
        )(params["router"], params["w_gate"], params["w_in"], params["w_out"], x)
        return out, aux

    # unsharded oracle path
    capacity = max(8, int(b * s * top_k / n_experts * capacity_factor))
    out, aux = _dispatch_local(
        x.reshape(-1, d),
        params["router"],
        params["w_gate"],
        params["w_in"],
        params["w_out"],
        jnp.int32(0),
        top_k=top_k,
        capacity=capacity,
        split=expert_split,
    )
    return out.reshape(b, s, d), aux
