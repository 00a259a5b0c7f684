"""The benchmark's own yardstick: data, arrivals, ground truth and the
window arithmetic.

Everything here is a copy, not a call, so that a change to the program
(``src/repro``) cannot move what it is measured with:

* ``catalog`` / ``queries`` copy ``data/synthetic.py``'s ``mips_dataset``
  norm profiles and ``mips_queries``;
* ``arrival_times`` keeps ``poisson_trace``'s exponential-gap arithmetic,
  with one change for steadiness: every seed gets the same multiset of
  gaps, in its own order, so every run carries the same amount of work;
* ``exact_topk`` is a plain matmul at ``Precision.HIGHEST`` plus
  ``lax.top_k`` in query tiles (or, for the control, the same over int8
  codes), and ``recall_at_k`` the paper's recall;
* ``served_score_errors`` re-scores every served (id, score) pair in
  float64 on the host.
"""
from __future__ import annotations

import functools

import numpy as np

# Streams drawn from one run's --seed; the gap multiset is seed-independent.
_CATALOG, _QUERIES, _ORDER, _GAPS = 1, 2, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def catalog(n: int, d: int, profile: str, seed: int) -> np.ndarray:
    """[n, d] float32 items; rows N(0, 1/d), scaled per the norm profile."""
    r = rng(seed, _CATALOG)
    x = r.normal(size=(n, d)).astype(np.float32) / np.sqrt(d)
    if profile == "lognormal":
        x = x * r.lognormal(mean=0.0, sigma=0.6, size=(n, 1)).astype(
            np.float32)
    elif profile != "gaussian":
        raise ValueError(f"unknown norm profile {profile!r}")
    return x


def queries(n: int, d: int, seed: int) -> np.ndarray:
    """[n, d] float32 queries, rows N(0, 1/d), i.i.d. from the seed."""
    r = rng(seed, _QUERIES)
    return (r.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)


def arrival_times(rate_qps: float, seconds: float, seed: int) -> np.ndarray:
    """Open-loop Poisson due times filling [0, seconds).

    ``round(rate * seconds)`` exponential gaps at ``rate`` are drawn from a
    fixed stream and put in the seed's order; the first request is due at 0,
    each later one a gap after the one before, and the last gap runs to the
    window's end.  The gaps are scaled to sum to the window, so the offered
    rate is exact and every seed offers the same gaps."""
    n = int(round(rate_qps * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_qps}/s offers no request in {seconds}s")
    gaps = rng(0, _GAPS).exponential(1.0 / rate_qps, size=n)
    gaps = gaps[rng(seed, _ORDER).permutation(n)] * (seconds / gaps.sum())
    return np.cumsum(gaps) - gaps


def _int8(x):
    """Symmetric per-row int8 codes and their float32 scales."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-30)
    scale = scale / 127.0
    return jnp.round(x / scale).astype(jnp.int8), scale


@functools.lru_cache(maxsize=None)
def _topk_tile(k: int, precision: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tile(q, x):
        if precision == "int8":
            (cq, sq), (cx, sx) = _int8(q), _int8(x)
            s = jnp.einsum("bd,nd->bn", cq, cx,
                           preferred_element_type=jnp.int32)
            s = s.astype(jnp.float32) * sq * sx[:, 0][None, :]
        else:
            s = jnp.einsum("bd,nd->bn", q, x,
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(s, k)

    return tile


def exact_topk(q: np.ndarray, items, k: int = 10, tile: int = 512,
               precision: str = "float32"):
    """Exact top-k of every row of ``q`` over ``items`` (a device array):
    (scores [B, k] float32, ids [B, k] int32), float32 products at
    ``Precision.HIGHEST``.  Tiles are padded to one shape so a single
    program serves every tile.  ``precision="int8"`` scores symmetric
    per-row int8 codes of both sides instead: the lower-precision control."""
    fn = _topk_tile(k, precision)
    b = q.shape[0]
    pad = (-b) % tile
    qp = np.concatenate([q, np.zeros((pad, q.shape[1]), q.dtype)])
    vals, ids = [], []
    for s in range(0, qp.shape[0], tile):
        v, i = fn(qp[s:s + tile], items)
        vals.append(np.asarray(v))
        ids.append(np.asarray(i))
    return np.concatenate(vals)[:b], np.concatenate(ids)[:b].astype(np.int32)


def recall_at_k(pred: np.ndarray, true: np.ndarray) -> np.ndarray:
    """Per-request recall@k: the share of ``true``'s k ids in ``pred``."""
    hit = (pred[:, :, None] == true[:, None, :]) & (true[:, None, :] >= 0)
    return hit.any(axis=1).sum(axis=-1) / true.shape[1]


def served_score_errors(q: np.ndarray, items: np.ndarray, ids: np.ndarray,
                        scores: np.ndarray, block: int = 2048) -> np.ndarray:
    """Per-request worst error of the served answer, in [0, 1].

    For every served (id, score): |score - q.x_id| / (|q| |x_id|), with the
    inner product in float64 (the Cauchy-Schwarz scale makes it a relative
    error of the dot product itself).  An answer that is not a top-k list
    at all reads 1: an id out of range or repeated, or scores that do not
    descend."""
    n = items.shape[0]
    out = np.ones(ids.shape[0])
    for s in range(0, ids.shape[0], block):
        i = ids[s:s + block]
        sc = scores[s:s + block].astype(np.float64)
        ok = (i >= 0) & (i < n)
        safe = np.where(ok, i, 0)
        x = items[safe].astype(np.float64)                    # [b, k, d]
        qq = q[s:s + block].astype(np.float64)
        exact = np.einsum("bd,bkd->bk", qq, x)
        scale = (np.linalg.norm(qq, axis=1)[:, None]
                 * np.linalg.norm(x, axis=2))
        err = np.abs(sc - exact) / np.maximum(scale, 1e-30)
        err = np.where(ok & np.isfinite(sc), np.minimum(err, 1.0), 1.0)
        srt = np.sort(i, axis=1)
        distinct = (srt[:, 1:] != srt[:, :-1]).all(axis=1)
        with np.errstate(invalid="ignore"):   # -inf scores of empty slots
            descending = (np.diff(sc, axis=1) <= 0).all(axis=1)
        worst = err.max(axis=1)
        out[s:s + block] = np.where(distinct & descending, worst, 1.0)
    return out


def p95_ms(latencies_s: np.ndarray) -> float:
    """95th percentile of the latencies, in ms (numpy's linear rule)."""
    return float(np.percentile(np.asarray(latencies_s) * 1e3, 95))


def completed_qps(finish_t: np.ndarray, seconds: float) -> float:
    """Requests finished inside [0, seconds), per second of the window."""
    return float(np.count_nonzero(np.asarray(finish_t) < seconds) / seconds)
