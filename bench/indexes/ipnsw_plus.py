"""The ``ipnsw_plus`` index kind: ip-NSW+ (arXiv:1909.13459, section 4),
an angular graph whose walk seeds the inner-product graph's walk.

Keys of ``index`` in a configuration that names it: ip-NSW's
``GRAPH_KEYS`` for the inner-product graph, and ``ang_degree``, ``ang_ef``
and ``k_angular`` for the angular graph and its seeds.
"""
from __future__ import annotations

from bench.indexes.ipnsw import GRAPH_KEYS


def make(config: dict):
    """The configuration's index, with the fused kernels of the chip path."""
    from repro.core import IpNSWPlus

    p = config["index"]
    return IpNSWPlus(ang_degree=p["ang_degree"], ang_ef=p["ang_ef"],
                     k_angular=p["k_angular"],
                     **{k: p[k] for k in GRAPH_KEYS})


def build(index, items) -> None:
    """Build on the device array ``items``; returns once both graphs'
    adjacency is on the device."""
    import jax

    index.build(items)
    jax.block_until_ready([index.ang_graph.adj, index.ip_graph.adj])
