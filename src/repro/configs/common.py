"""Arch -> (step_fn, abstract inputs, shardings) cell builders.

Every assigned architecture exposes ``build_cell(shape_name, mesh) -> Cell``;
the dry-run jits/lowers/compiles the cell on the production mesh, the
roofline reads its cost analysis, and smoke tests run REDUCED configs of the
same families through the same step functions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.launch.mesh import batch_axes_of, data_parallelism
from repro.models import gnn as gnn_mod
from repro.models import layers as layers_mod
from repro.models import transformer as tf
from repro.models.recsys import dien as dien_mod
from repro.models.recsys import dlrm as dlrm_mod
from repro.models.recsys import mind as mind_mod
from repro.models.recsys import sasrec as sasrec_mod
from repro.train.optimizer import adamw_init, adamw_specs, adamw_update, cosine_schedule

SDS = jax.ShapeDtypeStruct


class Cell(NamedTuple):
    name: str                 # "<arch>/<shape>"
    step_fn: Callable
    args: tuple               # abstract inputs (ShapeDtypeStructs)
    in_specs: tuple           # PartitionSpec pytrees matching args
    out_specs: Any            # PartitionSpec pytree (or None to infer)
    meta: dict                # roofline metadata (model_flops etc.)
    donate: tuple = ()        # argnums donated (in-place update buffers)


def _is_spec(x):
    return isinstance(x, P)


def shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=_is_spec
    )


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# LM transformers
# ---------------------------------------------------------------------------

LM_SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


@dataclasses.dataclass
class LMArch:
    arch_id: str
    cfg: tf.TransformerConfig
    family: str = "lm"

    def shape_names(self):
        names = ["train_4k", "prefill_32k", "decode_32k"]
        if any(w is not None for w in self.cfg.window_pattern):
            names.append("long_500k")  # sub-quadratic archs only
        return names

    def build_cell(self, shape_name: str, mesh) -> Cell:
        layers_mod.set_batch_axes_for_mesh(mesh)
        sh = LM_SHAPES[shape_name]
        cfg = self.cfg
        batch_ax = batch_axes_of(mesh)
        all_ax = tuple(mesh.axis_names)
        params_abs = tf.abstract_params(cfg)
        pspecs = tf.specs(cfg)
        b, s = sh["batch"], sh["seq"]
        meta = dict(
            family="lm",
            arch=self.arch_id,
            shape=shape_name,
            kind=sh["kind"],
            params=cfg.param_count(),
            active_params=cfg.active_param_count(),
            tokens=b * s if sh["kind"] != "decode" else b,
        )

        if sh["kind"] == "train":
            # §Perf iteration 3: dense-LM training is pure-FSDP on the
            # single-pod mesh — the batch spans BOTH axes (1 seq/chip), so
            # the per-layer collectives are weight gathers (~2 x params/256)
            # instead of Megatron-TP activation gathers (~8 x B_loc*S*d).
            # MoE archs keep the hybrid (tokens must stay replicated over
            # "model" for the expert dispatch); multi-pod keeps TP+SP
            # (global batch 256 < 512 chips).
            fsdp = (not cfg.is_moe) and "pod" not in mesh.axis_names
            train_batch_ax = ("data", "model") if fsdp else batch_ax
            layers_mod.set_batch_axes(train_batch_ax)
            opt_abs = jax.eval_shape(adamw_init, params_abs)
            ospecs = adamw_specs(pspecs)
            batch_abs = {
                "tokens": SDS((b, s), jnp.int32),
                "labels": SDS((b, s), jnp.int32),
            }
            bspecs = {
                "tokens": P(train_batch_ax, None),
                "labels": P(train_batch_ax, None),
            }

            def train_step(params, opt, batch):
                loss, grads = jax.value_and_grad(tf.lm_loss)(
                    params, batch, cfg, mesh
                )
                lr = cosine_schedule(
                    opt.step, base_lr=3e-4, warmup=2000, total=100_000
                )
                new_p, new_o = adamw_update(grads, opt, params, lr=lr)
                return new_p, new_o, loss

            return Cell(
                name=f"{self.arch_id}/{shape_name}",
                step_fn=train_step,
                args=(params_abs, opt_abs, batch_abs),
                in_specs=(pspecs, ospecs, bspecs),
                out_specs=(pspecs, ospecs, P()),
                meta=meta,
                donate=(0, 1),
            )

        if sh["kind"] == "prefill":
            tokens_abs = SDS((b, s), jnp.int32)
            cspecs = tf.cache_specs(cfg, batch=batch_ax, seq=("model",))

            def prefill_step(params, tokens):
                return tf.serve_prefill(params, tokens, cfg, mesh, max_len=s)

            return Cell(
                name=f"{self.arch_id}/{shape_name}",
                step_fn=prefill_step,
                args=(params_abs, tokens_abs),
                in_specs=(pspecs, P(batch_ax, None)),
                out_specs=((P(batch_ax, "model")), cspecs),
                meta=meta,
            )

        # decode
        long_ctx = b == 1
        cache_batch = () if long_ctx else batch_ax
        cache_seq = all_ax if long_ctx else ("model",)
        caches_abs = jax.eval_shape(
            functools.partial(tf.init_cache, cfg, b, sh["seq"])
        )
        cspecs = tf.cache_specs(cfg, batch=cache_batch, seq=cache_seq)
        token_abs = SDS((b, 1), jnp.int32)
        off_abs = SDS((), jnp.int32)

        def decode_step(params, caches, token, q_offset):
            return tf.serve_step(params, caches, token, q_offset, cfg, mesh)

        tok_spec = P(None, None) if long_ctx else P(batch_ax, None)
        logit_spec = P(None, "model") if long_ctx else P(batch_ax, "model")
        return Cell(
            name=f"{self.arch_id}/{shape_name}",
            step_fn=decode_step,
            args=(params_abs, caches_abs, token_abs, off_abs),
            in_specs=(pspecs, cspecs, tok_spec, P()),
            out_specs=(logit_spec, cspecs),
            meta=meta,
            donate=(1,),
        )


# ---------------------------------------------------------------------------
# GNN (meshgraphnet)
# ---------------------------------------------------------------------------

GNN_SHAPES = {
    # (n_nodes, n_edges, d_feat, note)
    "full_graph_sm": dict(n_nodes=2_708, n_edges=10_556, d_feat=1_433),
    "minibatch_lg": dict(n_nodes=169_984, n_edges=168_960, d_feat=602),
    "ogb_products": dict(n_nodes=2_449_029, n_edges=61_859_140, d_feat=100),
    "molecule": dict(n_nodes=30 * 128, n_edges=64 * 128, d_feat=16),
}


@dataclasses.dataclass
class GNNArch:
    arch_id: str
    base: gnn_mod.GNNConfig
    family: str = "gnn"

    def shape_names(self):
        return list(GNN_SHAPES)

    def config_for(self, shape_name: str) -> gnn_mod.GNNConfig:
        sh = GNN_SHAPES[shape_name]
        return dataclasses.replace(self.base, d_feat=sh["d_feat"])

    def build_cell(self, shape_name: str, mesh) -> Cell:
        sh = GNN_SHAPES[shape_name]
        cfg = self.config_for(shape_name)
        ndev = mesh.devices.size
        n = _round_up(sh["n_nodes"], ndev)
        e = _round_up(sh["n_edges"], ndev)
        axes = tuple(mesh.axis_names)

        params_abs = jax.eval_shape(
            lambda k: gnn_mod._init_params(k, cfg), jax.random.PRNGKey(0)
        )
        pspecs = gnn_mod.specs(cfg)
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        ospecs = adamw_specs(pspecs)

        graph_abs = {
            "node_feat": SDS((n, cfg.d_feat), jnp.float32),
            "edge_feat": SDS((e, cfg.d_edge), jnp.float32),
            "src": SDS((e,), jnp.int32),
            "dst": SDS((e,), jnp.int32),
            "targets": SDS((n, cfg.out_dim), jnp.float32),
        }
        gspecs = gnn_mod.data_specs(axes)

        def train_step(params, opt, graph):
            loss, grads = jax.value_and_grad(gnn_mod.mse_loss)(
                params, graph, cfg, mesh
            )
            lr = cosine_schedule(opt.step, base_lr=1e-3, warmup=100, total=10_000)
            new_p, new_o = adamw_update(grads, opt, params, lr=lr)
            return new_p, new_o, loss

        meta = dict(
            family="gnn",
            arch=self.arch_id,
            shape=shape_name,
            kind="train",
            n_nodes=n,
            n_edges=e,
            d_hidden=cfg.d_hidden,
            n_layers=cfg.n_layers,
        )
        return Cell(
            name=f"{self.arch_id}/{shape_name}",
            step_fn=train_step,
            args=(params_abs, opt_abs, graph_abs),
            in_specs=(pspecs, ospecs, gspecs),
            out_specs=(pspecs, ospecs, P()),
            meta=meta,
            donate=(0, 1),
        )


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------

RECSYS_SHAPES = {
    "train_batch": dict(kind="train", batch=65_536),
    "serve_p99": dict(kind="serve", batch=512, n_cand=1_000),
    "serve_bulk": dict(kind="serve", batch=262_144, n_cand=1_000),
    "retrieval_cand": dict(kind="retrieval", batch=1, n_cand=1_000_000),
}

_RECSYS_MODS = {
    "dlrm-rm2": dlrm_mod,
    "sasrec": sasrec_mod,
    "mind": mind_mod,
    "dien": dien_mod,
}


@dataclasses.dataclass
class RecsysArch:
    arch_id: str
    cfg: Any
    family: str = "recsys"

    @property
    def mod(self):
        return _RECSYS_MODS[self.arch_id]

    def shape_names(self):
        return list(RECSYS_SHAPES)

    # ---- batch builders per model kind ------------------------------------

    def _train_batch(self, b):
        cfg = self.cfg
        if self.arch_id == "dlrm-rm2":
            abs_ = {
                "dense": SDS((b, cfg.n_dense), jnp.float32),
                "sparse": SDS((b, cfg.n_sparse), jnp.int32),
                "labels": SDS((b,), jnp.float32),
            }
        elif self.arch_id == "sasrec":
            s = cfg.seq_len
            abs_ = {
                "hist": SDS((b, s), jnp.int32),
                "pos": SDS((b, s), jnp.int32),
                "neg": SDS((b, s, 4), jnp.int32),
            }
        elif self.arch_id == "mind":
            s = cfg.seq_len
            abs_ = {
                "hist": SDS((b, s), jnp.int32),
                "pos": SDS((b,), jnp.int32),
                "neg": SDS((b, 20), jnp.int32),
            }
        else:  # dien
            s = cfg.seq_len
            abs_ = {
                "hist": SDS((b, s), jnp.int32),
                "target": SDS((b,), jnp.int32),
                "labels": SDS((b,), jnp.float32),
                "aux_neg": SDS((b, s), jnp.int32),
            }
        return abs_

    def loss_fn(self):
        return {
            "dlrm-rm2": dlrm_mod.bce_loss,
            "sasrec": sasrec_mod.sampled_softmax_loss,
            "mind": mind_mod.sampled_softmax_loss,
            "dien": dien_mod.bce_loss,
        }[self.arch_id]

    def build_cell(self, shape_name: str, mesh) -> Cell:
        layers_mod.set_batch_axes_for_mesh(mesh)
        sh = RECSYS_SHAPES[shape_name]
        cfg = self.cfg
        batch_ax = batch_axes_of(mesh)
        mod = self.mod
        params_abs = jax.eval_shape(
            lambda k: mod._init_params(k, cfg), jax.random.PRNGKey(0)
        )
        pspecs = mod.specs(cfg)
        b = sh["batch"]
        meta = dict(
            family="recsys", arch=self.arch_id, shape=shape_name, kind=sh["kind"],
            batch=b, n_cand=sh.get("n_cand", 0),
        )

        if sh["kind"] == "train":
            opt_abs = jax.eval_shape(adamw_init, params_abs)
            ospecs = adamw_specs(pspecs)
            batch_abs = self._train_batch(b)
            bspecs = jax.tree.map(
                lambda a: P(batch_ax, *([None] * (len(a.shape) - 1))),
                batch_abs,
            )
            loss_fn = self.loss_fn()

            def train_step(params, opt, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
                lr = cosine_schedule(opt.step, base_lr=1e-3, warmup=500, total=50_000)
                new_p, new_o = adamw_update(grads, opt, params, lr=lr)
                return new_p, new_o, loss

            return Cell(
                name=f"{self.arch_id}/{shape_name}",
                step_fn=train_step,
                args=(params_abs, opt_abs, batch_abs),
                in_specs=(pspecs, ospecs, bspecs),
                out_specs=(pspecs, ospecs, P()),
                meta=meta,
                donate=(0, 1),
            )

        if sh["kind"] == "serve":
            nc = sh["n_cand"]
            # §Perf: serving REPLICATES the item-embedding table when it is
            # small (sasrec/mind/dien: 72-256 MB) — candidate-gather lookups
            # become local instead of cross-shard collectives.  Training
            # keeps tables row-sharded (optimizer state).  DLRM's 26 x 1M
            # tables (6.7 GB) stay sharded.
            if self.arch_id != "dlrm-rm2" and "item_emb" in pspecs:
                pspecs = dict(pspecs)
                pspecs["item_emb"] = P(None, None)
            if self.arch_id == "dlrm-rm2":
                batch_abs = {
                    "dense": SDS((b, cfg.n_dense), jnp.float32),
                    "sparse": SDS((b, cfg.n_sparse), jnp.int32),
                }
                bspecs = {
                    "dense": P(batch_ax, None),
                    "sparse": P(batch_ax, None),
                }

                def serve_step(params, batch):
                    return dlrm_mod.forward(params, batch, cfg)

                out_spec = P(batch_ax)
                args = (params_abs, batch_abs)
                in_specs = (pspecs, bspecs)
            elif self.arch_id == "dien":
                batch_abs = {
                    "hist": SDS((b, cfg.seq_len), jnp.int32),
                    "target": SDS((b,), jnp.int32),
                }
                bspecs = {"hist": P(batch_ax, None), "target": P(batch_ax)}

                def serve_step(params, batch):
                    logit, _ = dien_mod.forward(params, batch, cfg)
                    return logit

                out_spec = P(batch_ax)
                args = (params_abs, batch_abs)
                in_specs = (pspecs, bspecs)
            else:  # sasrec / mind: re-rank nc candidates per user
                s = cfg.seq_len
                batch_abs = {
                    "hist": SDS((b, s), jnp.int32),
                    "cand": SDS((b, nc), jnp.int32),
                }
                bspecs = {"hist": P(batch_ax, None), "cand": P(batch_ax, None)}
                rerank = _make_rerank(mod, self.arch_id, cfg)
                serve_step = rerank
                out_spec = P(batch_ax, None)
                args = (params_abs, batch_abs)
                in_specs = (pspecs, bspecs)

            return Cell(
                name=f"{self.arch_id}/{shape_name}",
                step_fn=serve_step,
                args=args,
                in_specs=in_specs,
                out_specs=out_spec,
                meta=meta,
            )

        # retrieval_cand: 1 user vs 1M candidates
        nc = sh["n_cand"]
        if self.arch_id == "dlrm-rm2":
            # bulk candidate scoring through the ranker: 1M candidate rows
            batch_abs = {
                "dense": SDS((nc, cfg.n_dense), jnp.float32),
                "sparse": SDS((nc, cfg.n_sparse), jnp.int32),
            }
            bspecs = {"dense": P(batch_ax, None), "sparse": P(batch_ax, None)}

            def retrieval_step(params, batch):
                scores = dlrm_mod.forward(params, batch, cfg)
                vals, ids = jax.lax.top_k(scores, 100)
                return {"scores": vals, "ids": ids}

            out_spec = {"scores": P(None), "ids": P(None)}
            args = (params_abs, batch_abs)
            in_specs = (pspecs, bspecs)
        else:
            s = cfg.seq_len
            hist_abs = SDS((b, s), jnp.int32)
            msize = mesh.shape.get("model", 1)
            shard_topk = msize > 1 and cfg.n_items % msize == 0

            def _user_vectors(params, hist):
                """[B, K, d] user-side query vectors (K=1 except MIND)."""
                if self.arch_id == "mind":
                    return mind_mod.interest_capsules(params, hist, cfg)
                if self.arch_id == "sasrec":
                    return sasrec_mod.user_embedding(params, hist, cfg)[:, None]
                # dien
                mask = hist >= 0
                e = jnp.take(params["item_emb"], jnp.maximum(hist, 0), axis=0)
                states = dien_mod._run_gru(params["gru1"], e, mask, cfg.gru_dim)
                lengths = jnp.maximum(jnp.sum(mask, axis=1) - 1, 0)
                h_last = jnp.take_along_axis(
                    states, lengths[:, None, None], axis=1
                )[:, 0]
                return (h_last @ params["attn_w"].T)[:, None]

            if shard_topk:
                # §Perf: shard-LOCAL top-k + tiny merge — the baseline
                # gathers the full [B, n_items] score row (4 MB) to run a
                # global top-k; this gathers 2*P*k*B values (~13 KB).
                def retrieval_step(params, hist):
                    u = _user_vectors(params, hist)

                    def body(emb_blk, u):
                        sc = jnp.einsum(
                            "bkd,nd->bkn", u, emb_blk,
                            preferred_element_type=jnp.float32,
                        )
                        sc = jnp.max(sc, axis=1)              # over interests
                        vals, idx = jax.lax.top_k(sc, 100)
                        off = jax.lax.axis_index("model") * emb_blk.shape[0]
                        idx = idx + off
                        allv = jax.lax.all_gather(vals, "model")  # [P, B, k]
                        alli = jax.lax.all_gather(idx, "model")
                        p_, b_, k_ = allv.shape
                        allv = jnp.moveaxis(allv, 0, 1).reshape(b_, p_ * k_)
                        alli = jnp.moveaxis(alli, 0, 1).reshape(b_, p_ * k_)
                        mv, sel = jax.lax.top_k(allv, 100)
                        return mv, jnp.take_along_axis(alli, sel, axis=-1)

                    vals, ids = shard_map(
                        body,
                        mesh=mesh,
                        in_specs=(P("model", None), P(None, None, None)),
                        out_specs=(P(None, None), P(None, None)),
                        check_vma=False,
                    )(params["item_emb"], u)
                    return {"scores": vals, "ids": ids}
            else:
                def retrieval_step(params, hist):
                    scores = mod.retrieval_scores(params, hist, cfg)
                    vals, ids = jax.lax.top_k(scores, 100)
                    return {"scores": vals, "ids": ids}

            out_spec = {"scores": P(None, None), "ids": P(None, None)}
            args = (params_abs, hist_abs)
            in_specs = (pspecs, P(None, None))

        return Cell(
            name=f"{self.arch_id}/{shape_name}",
            step_fn=retrieval_step,
            args=args,
            in_specs=in_specs,
            out_specs=out_spec,
            meta=meta,
        )


def _make_rerank(mod, arch_id, cfg):
    def rerank(params, batch):
        cand_e = jnp.take(
            params["item_emb"], jnp.maximum(batch["cand"], 0), axis=0
        )  # [B, nc, d]
        if arch_id == "mind":
            interests = mind_mod.interest_capsules(params, batch["hist"], cfg)
            sc = jnp.einsum("bkd,bnd->bkn", interests, cand_e)
            return jnp.max(sc, axis=1)
        u = sasrec_mod.user_embedding(params, batch["hist"], cfg)
        return jnp.einsum("bd,bnd->bn", u, cand_e)

    return rerank
