"""Step factory: (arch x shape) -> the exact callable the dry-run lowers,
the trainer executes, and the smoke tests run at reduced scale.

Train steps: state {"params", "opt"} x batch -> (state, metrics), AdamW.
Serve steps: family-specific (prefill/decode/scoring/retrieval).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchSpec
from ..configs.shapes import (GNN_SHAPE_DEFS, LM_SHAPE_DEFS,
                              RECSYS_SHAPE_DEFS, input_specs)
from ..models import recsys as R
from ..models import schnet as S
from ..models import transformer as T
from ..train.optimizer import AdamWConfig, adamw_init, adamw_update
from ..sparse_ops import embedding_bag

TOPK_SERVE = 100


def _topk(scores, k=TOPK_SERVE):
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))


def adapt_config(arch: ArchSpec, shape: str, cfg=None):
    """Per-shape config adjustments (SchNet graph-mode d_feat/classes)."""
    import dataclasses
    cfg = cfg if cfg is not None else arch.config()
    if arch.family == "gnn" and shape != "molecule":
        d = GNN_SHAPE_DEFS[shape]
        return dataclasses.replace(cfg, d_feat=d["d_feat"],
                                    n_out=d["classes"])
    return cfg


def init_fn(arch: ArchSpec, shape: str, cfg):
    fam = arch.family
    if fam == "lm":
        return lambda key: T.init_params(cfg, key)
    if fam == "gnn":
        return lambda key: S.init_params(cfg, key)
    if isinstance(cfg, R.DLRMConfig):
        return lambda key: R.init_dlrm(cfg, key)
    if isinstance(cfg, R.DINConfig):
        return lambda key: R.init_din(cfg, key)
    if isinstance(cfg, R.TwoTowerConfig):
        return lambda key: R.init_two_tower(cfg, key)
    if isinstance(cfg, R.Bert4RecConfig):
        return lambda key: R.init_bert4rec(cfg, key)
    raise TypeError(type(cfg))


def loss_fn(arch: ArchSpec, shape: str, cfg, rules: T.Rules):
    fam = arch.family
    if fam == "lm":
        return lambda p, b: T.lm_loss(cfg, p, b, rules)
    if fam == "gnn":
        if shape == "molecule":
            return lambda p, b: S.molecule_loss(cfg, p, b)
        return lambda p, b: S.node_loss(cfg, p, b)
    if isinstance(cfg, R.DLRMConfig):
        return lambda p, b: R.dlrm_loss(cfg, p, b, rules)
    if isinstance(cfg, R.DINConfig):
        return lambda p, b: R.din_loss(cfg, p, b, rules)
    if isinstance(cfg, R.TwoTowerConfig):
        return lambda p, b: R.two_tower_loss(cfg, p, b, rules)
    if isinstance(cfg, R.Bert4RecConfig):
        return lambda p, b: R.bert4rec_loss(cfg, p, b, rules)
    raise TypeError(type(cfg))


def make_train_step(arch: ArchSpec, shape: str, cfg, rules: T.Rules,
                    opt_cfg: AdamWConfig | None = None,
                    grad_shardings=None):
    """``grad_shardings``: optional pytree of NamedSharding — constrains
    gradients to the optimizer-state layout right after autodiff, which
    turns GSPMD's full-gradient all-reduce into a reduce-scatter (ZeRO)."""
    opt_cfg = opt_cfg or AdamWConfig()
    lfn = loss_fn(arch, shape, cfg, rules)

    def step(state, batch):
        loss, grads = jax.value_and_grad(lfn)(state["params"], batch)
        if grad_shardings is not None:
            grads = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, grads, grad_shardings)
        params, opt, metrics = adamw_update(opt_cfg, grads, state["opt"],
                                            state["params"])
        metrics["loss"] = loss
        return {"params": params, "opt": opt}, metrics

    return step


# --------------------------------------------------------------------------
# serve steps
# --------------------------------------------------------------------------

def _dlrm_score_candidates(cfg, params, user, cand_ids, rules):
    """One user context x N candidate items (26th sparse field varies)."""
    n = cand_ids.shape[0]
    cd = cfg.compute_dtype
    bot = R._mlp(params["bot"], user["dense"].astype(cd), final_act=True)
    user_embs = [embedding_bag(params["tables"][f].astype(cd),
                               user["sparse"][:, f, :],
                               jnp.ones((1, cfg.multi_hot), cd))
                 for f in range(cfg.n_sparse - 1)]
    cand = jnp.take(params["tables"][cfg.n_sparse - 1], cand_ids,
                    axis=0).astype(cd)                        # [N, D]
    fixed = jnp.concatenate([bot] + user_embs, axis=0)        # [26, D]
    feats = jnp.concatenate(
        [jnp.broadcast_to(fixed[None], (n,) + fixed.shape), cand[:, None]],
        axis=1)                                               # [N, 27, D]
    inter = jnp.einsum("bnd,bmd->bnm", feats, feats)
    iu, ju = jnp.triu_indices(feats.shape[1], k=1)
    flat = inter[:, iu, ju]
    top_in = jnp.concatenate(
        [jnp.broadcast_to(bot, (n, bot.shape[1])), flat], axis=-1)
    return R._mlp(params["top"], top_in)[:, 0]


def make_serve_step(arch: ArchSpec, shape: str, cfg, rules: T.Rules,
                    mesh=None, sharded_topk: bool = False):
    fam = arch.family
    spec = None
    if fam == "lm":
        kind = LM_SHAPE_DEFS[shape]["kind"]
        if kind == "prefill":
            max_len = LM_SHAPE_DEFS[shape]["seq"]

            def step(params, tokens):
                return T.prefill(cfg, params, tokens, max_len, rules)
            return step
        if kind == "decode":
            def step(params, token, cache, cache_len):
                return T.decode_step(cfg, params, token, cache, cache_len,
                                     rules)
            return step
        raise ValueError(f"no serve step for LM shape {shape}")
    if fam == "gnn":
        raise ValueError("GNN cells are train-step cells")
    del spec
    kind = RECSYS_SHAPE_DEFS[shape]["kind"]
    if isinstance(cfg, R.DLRMConfig):
        if kind == "serve":
            return lambda params, batch: R.dlrm_forward(cfg, params, batch,
                                                        rules)
        def dlrm_retr(params, user, cand_ids):
            s = _dlrm_score_candidates(cfg, params, user, cand_ids, rules)
            vals, idx = _topk(s)
            return vals, cand_ids[idx]
        return dlrm_retr
    if isinstance(cfg, R.DINConfig):
        if kind == "serve":
            return lambda params, batch: R.din_forward(cfg, params, batch,
                                                       rules)
        def din_retr(params, hist, cand_ids):
            n = cand_ids.shape[0]
            batch = {"hist": jnp.broadcast_to(hist, (n, hist.shape[1])),
                     "target": cand_ids}
            s = R.din_forward(cfg, params, batch, rules)
            vals, idx = _topk(s)
            return vals, cand_ids[idx]
        return din_retr
    if isinstance(cfg, R.TwoTowerConfig):
        if kind == "serve":
            def tt_serve(params, user_feats, shortlist):
                u = R.user_encode(cfg, params, user_feats, rules)
                v = R.item_encode(cfg, params, shortlist, rules)
                return u @ v.T
            return tt_serve
        if sharded_topk and mesh is not None:
            from jax.sharding import PartitionSpec as P
            axes = tuple(mesh.axis_names)
            import numpy as _np
            n_shards = int(_np.prod([mesh.shape[a] for a in axes]))

            def tt_retr_sharded(params, user_feats, cand_emb):
                u = R.user_encode(cfg, params, user_feats, rules)[0]
                local_n = cand_emb.shape[0] // n_shards
                kk = min(TOPK_SERVE, local_n)

                def local(ce, uu):
                    s = ce @ uu
                    v, i = jax.lax.top_k(s, kk)
                    flat = jax.lax.axis_index(axes[0])
                    for a in axes[1:]:
                        flat = flat * mesh.shape[a] + jax.lax.axis_index(a)
                    return v, i + flat * local_n

                v, i = jax.shard_map(local, mesh=mesh,
                                     in_specs=(P(axes, None), P()),
                                     out_specs=(P(axes), P(axes)),
                                     check_vma=False)(cand_emb, u)
                tv, ti = jax.lax.top_k(v, TOPK_SERVE)
                return tv, i[ti]
            return tt_retr_sharded

        def tt_retr(params, user_feats, cand_emb):
            s = R.two_tower_score_candidates(cfg, params, user_feats,
                                             cand_emb, rules)
            return _topk(s)
        return tt_retr
    if isinstance(cfg, R.Bert4RecConfig):
        if kind == "serve":
            return lambda params, items, cand_ids: R.bert4rec_score_catalog(
                cfg, params, items, cand_ids, rules)
        def b4r_retr(params, items, cand_ids):
            s = R.bert4rec_score_catalog(cfg, params, items, cand_ids,
                                         rules)[0]
            vals, idx = _topk(s)
            return vals, cand_ids[idx]
        return b4r_retr
    raise TypeError(type(cfg))


def state_specs(arch: ArchSpec, shape: str, cfg):
    """ShapeDtypeStructs of the train state (no allocation)."""
    init = init_fn(arch, shape, cfg)
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: adamw_init(
        jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), params)))
    return {"params": params, "opt": opt}


# --------------------------------------------------------------------------
# smoke batches (small real data for reduced configs)
# --------------------------------------------------------------------------

def smoke_batch(arch: ArchSpec, shape: str, cfg, seed: int = 0):
    rng = np.random.default_rng(seed)
    fam = arch.family
    if fam == "lm":
        kind = LM_SHAPE_DEFS[shape]["kind"]
        b, s = 2, 32
        toks = rng.integers(1, cfg.vocab, (b, s + 1))
        if kind == "train":
            return {"batch": {"tokens": jnp.asarray(toks[:, :-1]),
                              "targets": jnp.asarray(toks[:, 1:])}}
        if kind == "prefill":
            return {"tokens": jnp.asarray(toks[:, :-1])}
        hkv, dh, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        cache = {"k": jnp.zeros((L, b, s, hkv, dh), cfg.compute_dtype),
                 "v": jnp.zeros((L, b, s, hkv, dh), cfg.compute_dtype)}
        return {"token": jnp.asarray(toks[:, :1]), "cache": cache,
                "cache_len": jnp.int32(s - 1)}
    if fam == "gnn":
        if shape == "molecule":
            b, n, e = 4, 8, 16
            return {"batch": {
                "z": jnp.asarray(rng.integers(1, cfg.n_atom_types, (b, n))),
                "pos": jnp.asarray(rng.standard_normal((b, n, 3)),
                                   jnp.float32),
                "edge_src": jnp.asarray(rng.integers(0, n, (b, e))),
                "edge_dst": jnp.asarray(rng.integers(0, n, (b, e))),
                "energy": jnp.asarray(rng.standard_normal(b), jnp.float32)}}
        nn, ee = 64, 256
        return {"batch": {
            "x": jnp.asarray(rng.standard_normal((nn, cfg.d_feat)),
                             jnp.float32),
            "edge_src": jnp.asarray(rng.integers(0, nn, ee)),
            "edge_dst": jnp.asarray(rng.integers(0, nn, ee)),
            "edge_dist": jnp.asarray(rng.random(ee) * cfg.cutoff,
                                     jnp.float32),
            "labels": jnp.asarray(rng.integers(0, cfg.n_out, nn)),
            "train_mask": jnp.ones(nn, jnp.float32)}}
    # recsys
    kind = RECSYS_SHAPE_DEFS[shape]["kind"]
    b = 8
    if isinstance(cfg, R.DLRMConfig):
        feats = {"dense": jnp.asarray(rng.standard_normal((b, cfg.n_dense)),
                                      jnp.float32),
                 "sparse": jnp.asarray(rng.integers(
                     0, cfg.vocab_per_field,
                     (b, cfg.n_sparse, cfg.multi_hot)))}
        if kind == "train":
            return {"batch": {**feats,
                              "label": jnp.asarray(rng.integers(0, 2, b))}}
        if kind == "serve":
            return {"batch": feats}
        return {"user": {"dense": feats["dense"][:1],
                         "sparse": feats["sparse"][:1, :cfg.n_sparse - 1]},
                "cand_ids": jnp.asarray(
                    rng.integers(0, cfg.vocab_per_field, 64))}
    if isinstance(cfg, R.DINConfig):
        base = {"hist": jnp.asarray(rng.integers(0, cfg.n_items,
                                                 (b, cfg.seq_len))),
                "target": jnp.asarray(rng.integers(0, cfg.n_items, b))}
        if kind == "train":
            return {"batch": {**base,
                              "label": jnp.asarray(rng.integers(0, 2, b))}}
        if kind == "serve":
            return {"batch": base}
        return {"hist": base["hist"][:1],
                "cand_ids": jnp.asarray(rng.integers(0, cfg.n_items, 64))}
    if isinstance(cfg, R.TwoTowerConfig):
        uf = jnp.asarray(rng.integers(1, cfg.n_user_feats,
                                      (b, cfg.user_bag)))
        if kind == "train":
            return {"batch": {
                "user_feats": uf,
                "pos_item": jnp.asarray(rng.integers(0, cfg.n_items, b)),
                "neg_items": jnp.asarray(
                    rng.integers(0, cfg.n_items, cfg.n_negatives)),
                "neg_logq": jnp.zeros(cfg.n_negatives, jnp.float32)}}
        if kind == "serve":
            return {"user_feats": uf,
                    "shortlist": jnp.asarray(rng.integers(0, cfg.n_items,
                                                          32))}
        return {"user_feats": uf[:1],
                "cand_emb": jnp.asarray(
                    rng.standard_normal((128, cfg.tower_mlp[-1])),
                    jnp.float32)}
    if isinstance(cfg, R.Bert4RecConfig):
        items = jnp.asarray(rng.integers(0, cfg.n_items, (b, cfg.seq_len)))
        if kind == "train":
            return {"batch": {
                "items": items,
                "targets": jnp.asarray(rng.integers(0, cfg.n_items,
                                                    (b, cfg.seq_len))),
                "mask": jnp.asarray(rng.integers(0, 2, (b, cfg.seq_len))),
                "neg_items": jnp.asarray(rng.integers(0, cfg.n_items, 64))}}
        cand = jnp.asarray(rng.integers(0, cfg.n_items, 32))
        if kind == "serve":
            return {"items": items, "cand_ids": cand}
        return {"items": items[:1], "cand_ids": cand}
    raise TypeError(type(cfg))
