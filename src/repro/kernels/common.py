"""Shared plumbing of the Pallas kernels: the interpret-mode decision and the
HBM layouts the gathering kernels (beam_step, commit_merge) DMA from.

Interpret mode is decided here and nowhere else: a kernel runs interpreted
exactly when JAX's default backend is the CPU (how the tests run), and is
compiled by Mosaic everywhere else.  There is no silent fallback: on an
accelerator the kernels compile or fail.

Layouts.  Mosaic tiles an HBM array's last two dims (8 x 128 words for
2-D f32/int32, 4 sublanes per word for int8), and a DMA may only slice
whole tiles.  A gather of ONE catalog row therefore needs an array whose
row is a whole tile:

  rows   ``[N, 1, w]`` — one item row per leading index (f32 rows, or int8
         codes packed four to an int32 word, see ``pack_codes``);
  packed ``[R, 1, 128]`` — a small per-node record (an adjacency row of
         ``mp`` slots, or one scalar of a column) stored ``128 // mp`` nodes
         to a 128-lane row; the kernel DMAs the whole row into SMEM and
         reads its node's lanes as scalars.

Zero padding of the feature axis keeps fp32 inner products bit-identical,
so every layout here is a drop-in for the unpadded reference math.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

LANES = 128
# Queries per grid step of the walk kernel: one sublane tile, so every
# per-query block is (8, x) and the merge network runs on 8 queries at once.
QUERY_TILE = 8


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one place interpret mode is decided: ``None`` means "interpret on
    the CPU backend, compile everywhere else".  An explicit bool is honored
    (the TPU compile tests pass ``False`` to lower for a described chip from
    a CPU process)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def slots_per_node(m: int) -> int:
    """Adjacency slots reserved per node in the packed layout: the smallest
    power of two >= ``m``, so a node's row never straddles a 128-lane row."""
    if not 1 <= m <= LANES:
        raise ValueError(f"degree must be in [1, {LANES}], got {m}")
    return 1 << (m - 1).bit_length()


def pack_adjacency(adj: jax.Array) -> jax.Array:
    """[N, M] int32 (-1 padded) -> [R, 1, 128] with node n's slot j at flat
    lane ``n * mp + j`` (``mp = slots_per_node(M)``, extra slots -1)."""
    n, m = adj.shape
    mp = slots_per_node(m)
    flat = jnp.pad(adj.astype(jnp.int32), ((0, 0), (0, mp - m)),
                   constant_values=-1).reshape(-1)
    flat = jnp.pad(flat, (0, round_up(flat.shape[0], LANES) - flat.shape[0]),
                   constant_values=-1)
    return flat.reshape(-1, 1, LANES)


def pack_column(col: jax.Array, dtype) -> jax.Array:
    """[N] -> [ceil(N / 128), 1, 128]: element n at row n >> 7, lane n & 127."""
    n = col.shape[0]
    flat = jnp.pad(col.astype(dtype), (0, round_up(n, LANES) - n))
    return flat.reshape(-1, 1, LANES)


def row_width(d: int, quantized: bool) -> int:
    """Padded feature width of the row layout: a lane multiple for f32; for
    int8 codes a multiple of 4 * 128, so the packed words fill whole lanes."""
    return round_up(d, 4 * LANES if quantized else LANES)


def pad_rows(x: jax.Array, width: int) -> jax.Array:
    """[N, d] -> [N, width] zero padded on the feature axis."""
    return jnp.pad(x, ((0, 0), (0, width - x.shape[-1])))


def f32_rows(items: jax.Array) -> jax.Array:
    """[N, d] -> [N, 1, dp] fp32 rows (dp = ``row_width(d, False)``)."""
    x = pad_rows(items.astype(jnp.float32), row_width(items.shape[-1], False))
    return x.reshape(x.shape[0], 1, x.shape[1])


def pack_codes(codes: jax.Array) -> jax.Array:
    """[N, d] int8 -> [N, 1, dp // 4] int32 (dp = ``row_width(d, True)``).

    Byte k of word i holds code ``k * (dp // 4) + i``: unpacking the four
    byte planes and concatenating them along lanes (``unpack_codes``)
    restores the codes in their natural order, so the kernel's dot sums the
    same products in the same order as the reference."""
    n, d = codes.shape
    dp = row_width(d, True)
    w = dp // 4
    u = pad_rows(codes.astype(jnp.int32), dp) & 0xFF
    u = u.reshape(n, 4, w).astype(jnp.uint32)
    word = u[:, 0] | (u[:, 1] << 8) | (u[:, 2] << 16) | (u[:, 3] << 24)
    return jax.lax.bitcast_convert_type(word, jnp.int32).reshape(n, 1, w)


def unpack_codes(words: jax.Array) -> jax.Array:
    """In-kernel inverse of ``pack_codes``: [R, w] int32 -> [R, 4w] fp32."""
    planes = [
        jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(words, jnp.int32(24 - 8 * k)), jnp.int32(24))
        for k in range(4)
    ]
    return jnp.concatenate(planes, axis=1).astype(jnp.float32)
