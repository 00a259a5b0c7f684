"""jit'd multi-head/batch wrapper: vmaps the single-head Pallas program over
batch and (kv-head x group) dims — the layout models/layers.py uses."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.kernel import flash_attention_head


@functools.partial(
    jax.jit, static_argnames=("q_offset", "window", "bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,   # [B, S, H, hd]
    k: jax.Array,   # [B, T, KV, hd]
    v: jax.Array,   # [B, T, KV, hd]
    *,
    q_offset: int = 0,
    window=None,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
):
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)

    head = functools.partial(
        flash_attention_head,
        q_offset=q_offset, window=window, bq=min(bq, s), bk=min(bk, t),
        interpret=interpret,
    )
    # vmap nesting (outside-in): batch 0, kv-head 1, group 1; k/v broadcast
    # over the group dim
    f_g = jax.vmap(head, in_axes=(1, None, None), out_axes=1)   # [S,G,hd]
    f_kv = jax.vmap(f_g, in_axes=(1, 1, 1), out_axes=1)         # [S,KV,G,hd]
    f_b = jax.vmap(f_kv, in_axes=(0, 0, 0), out_axes=0)
    out = f_b(qg, k, v)  # [B, S, KV, G, hd]
    return out.reshape(b, s, h, hd)
