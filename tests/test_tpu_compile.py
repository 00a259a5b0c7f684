"""Main-path Pallas kernels compile for a TPU v5e (Mosaic), at the widths
the one-chip serving configuration runs: d = 300 padded to 384 (512 for the
packed int8 codes), M = 16, ef up to 128, batch 256, N = 10^6.

Nothing runs: the chip is described (``v5e:2x2``), not attached, and each
kernel is lowered with ``interpret=False`` and compiled by the TPU compiler
installed with JAX.  What interpret mode cannot see — tile alignment of
blocks and DMA slices, scalar reads, VMEM/SMEM budgets — fails here.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library; the compiles stay in this process.
"""
import os

import pytest
import jax
import jax.numpy as jnp

N, D, M, B, EF = 1_000_000, 300, 16, 256, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _walk_shapes(one_chip, quantized, has_live, ef=EF, m=M):
    from repro.kernels.common import LANES, round_up, row_width, \
        slots_per_node

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = row_width(D, quantized)
    v = 10 * m + 1 + 2 * ef * m          # ip-NSW+ seeds + max_steps * M
    col = round_up(N, LANES) // LANES
    state = [s((B, ef), jnp.int32), s((B, ef), jnp.float32),
             s((B, ef), jnp.int32), s((B, 1), jnp.int32),
             s((B, v), jnp.int32), s((B, w), jnp.float32),
             s((round_up(N * slots_per_node(m), LANES) // LANES, 1, LANES),
               jnp.int32)]
    if quantized:
        state += [s((N, 1, w // 4), jnp.int32), s((col, 1, LANES), jnp.float32)]
    else:
        state += [s((N, 1, w), jnp.float32), None]
    state.append(s((col, 1, LANES), jnp.int32) if has_live else None)
    return state


@pytest.mark.parametrize(
    "quantized,has_live,ef,m",
    [(False, False, EF, M), (True, False, EF, M), (False, True, EF, M),
     (False, False, 10, 10)],
    ids=["f32", "int8", "live", "angular"],
)
def test_beam_step_compiles(one_chip, quantized, has_live, ef, m):
    from repro.kernels.beam_step.kernel import beam_step_pallas

    shapes = _walk_shapes(one_chip, quantized, has_live, ef=ef, m=m)
    args = [x for x in shapes if x is not None]
    scl_i = 8 if quantized else None
    live_i = len(args) - 1 if has_live else None

    def step(*a):
        return beam_step_pallas(
            *a[:8],
            a[scl_i] if scl_i is not None else None,
            a[live_i] if live_i is not None else None,
            degree=m, interpret=False)

    _compile(step, *args)


@pytest.mark.parametrize("tile", [1, 5, 8, 16, 32])
def test_commit_merge_compiles(one_chip, tile):
    from repro.kernels.commit_merge.kernel import commit_merge_pallas
    from repro.kernels.common import LANES, round_up, slots_per_node, \
        row_width

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    e = B * M
    g = round_up(e, tile)
    _compile(
        lambda *a: commit_merge_pallas(*a, degree=M, interpret=False),
        s((g // tile, tile, 1), jnp.int32),
        s((g // tile, tile, B), jnp.int32),
        s((g // tile, tile, B), jnp.float32),
        s((round_up(N * slots_per_node(M), LANES) // LANES, 1, LANES),
          jnp.int32),
        s((N, 1, row_width(D, False)), jnp.float32),
    )


def test_mips_topk_compiles(one_chip):
    from repro.kernels.mips_topk import ops as mips_ops

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(
        lambda q, x: mips_ops.mips_topk(q, x, k=10, interpret=False),
        s((B, D), jnp.float32), s((N, D), jnp.float32),
    )


def test_sharded_reference_walk_compiles(one_chip, monkeypatch):
    """The single-device sharded oracle maps the fused walk over the shards
    one at a time; a vmapped walk has no Mosaic lowering."""
    from repro.core.distributed import ShardedIndex, sharded_search_reference
    from repro.core.graph import GraphIndex

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p, nloc = 4, N // 4
    graph = GraphIndex(adj=s((p, nloc, M), jnp.int32),
                       items=s((p, nloc, D), jnp.float32),
                       size=s((p,), jnp.int32), entry=s((p,), jnp.int32),
                       entry_norm=s((p,), jnp.float32))
    index = ShardedIndex(ip=graph, ang=None, offset=s((p,), jnp.int32),
                         count=s((p,), jnp.int32),
                         gid=s((p, nloc), jnp.int32),
                         max_norm=s((p,), jnp.float32))
    # The kernels interpret on the CPU backend this process runs; steer
    # them to compile for the described chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _compile(
        lambda i, q: sharded_search_reference(
            i, q, k=10, ef=EF, plus=False, backend="pallas"),
        index, s((B, D), jnp.float32),
    )
