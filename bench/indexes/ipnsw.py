"""The ``ipnsw`` index kind: the paper's ip-NSW baseline (Morozov and
Babenko, NeurIPS 2018), one inner-product graph walked from its entry.

Keys of ``index`` in a configuration that names it: ``GRAPH_KEYS``.
"""
from __future__ import annotations

GRAPH_KEYS = ("max_degree", "ef_construction", "insert_batch", "backend",
              "build_backend", "commit_backend", "storage")


def make(config: dict):
    """The configuration's index, with the fused kernels of the chip path."""
    from repro.core import IpNSW

    p = config["index"]
    return IpNSW(**{k: p[k] for k in GRAPH_KEYS})


def build(index, items) -> None:
    """Build on the device array ``items``; returns once the graph's
    adjacency is on the device."""
    import jax

    index.build(items)
    jax.block_until_ready(index.graph.adj)
