"""The benchmark's bucket programs compile for a TPU v5e at the cells'
sizes: a catalog of Yahoo!Music's shape cut to N = 50,000 (d = 300),
both index kinds, every rung of the ladders (8, 32, 128) x ef 128 and (1, 8) x ef 32.

Nothing runs: the chip is described (``v5e:2x2``), not attached.  Each
whole serving program (``serve_loop._plus_bucket`` / ``_ipnsw_bucket``,
the walk kernel inside) is lowered with the kernels steered to Mosaic and
compiled by the TPU compiler installed with JAX, so a refusal shows here
before chip time is spent.  The topology is described inside a fixture,
never at import.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

N, D, K = 50_000, 300, 10
PLUS = dict(max_degree=16, ang_degree=10, ang_ef=10, k_angular=10)
RUNGS = [(8, 128), (32, 128), (128, 128), (1, 32), (8, 32)]


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
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _graph(one_chip, m):
    from repro.core.graph import GraphIndex

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return GraphIndex(adj=s((N, m), jnp.int32), items=s((N, D), jnp.float32),
                      size=s((), jnp.int32), entry=s((), jnp.int32),
                      entry_norm=s((), jnp.float32))


@pytest.mark.parametrize("kind", ["ipnsw_plus", "ipnsw"])
@pytest.mark.parametrize("batch,ef", RUNGS, ids=[f"{b}x{e}" for b, e in RUNGS])
def test_bucket_program_compiles(one_chip, monkeypatch, kind, batch, ef):
    from repro.launch import serve_loop as sl

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, valid = s((batch, D), jnp.float32), s((batch,), jnp.bool_)
    if kind == "ipnsw_plus":
        fn = functools.partial(
            sl._plus_bucket, k=K, ef=ef, ang_ef=PLUS["ang_ef"],
            k_angular=PLUS["k_angular"], backend="pallas", storage="f32")
        args = (_graph(one_chip, PLUS["ang_degree"]),
                _graph(one_chip, PLUS["max_degree"]), None, None, None, None,
                q, valid)
    else:
        fn = functools.partial(sl._ipnsw_bucket, k=K, ef=ef,
                               backend="pallas", storage="f32")
        args = (_graph(one_chip, PLUS["max_degree"]), None, None, None,
                q, valid)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # The bucket's temporaries (the per-dispatch [N, 1, 384] re-layout of
    # each graph among them) fit one chip's 16 GB many times over.
    assert mem.temp_size_in_bytes < 4 * 2**30
