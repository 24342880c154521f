"""Decoder-style transformer LM: RoPE / GQA / SwiGLU / RMSNorm, optional MoE
(sort-based static-capacity dispatch, EP-shardable), optional bidirectional
mode + learned positions, optional SPLADE-style sparse head (the learned
sparse encoder role for the retrieval core).

Layers are scanned with stacked parameters — HLO stays O(1) in depth.
Sharding is injected via ``Rules`` (logical-axis -> mesh-axes) through
with_sharding_constraint; `None` rules mean single-device execution (tests,
smoke configs).

Mixed precision: parameters are stored in ``param_dtype`` (fp32 by default),
compute runs in ``compute_dtype`` (bf16 by default).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int | None = None
    moe: MoEConfig | None = None
    causal: bool = True
    rope: bool = True
    max_position: int = 0      # >0: learned positional embeddings
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    sparse_head: bool = False  # SPLADE-style log1p-relu-maxpool head
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"  # full|dots (full = recompute layer in bwd)
    attn_chunk: int = 0        # >0: flash-style q-chunked attention
    kv_quant: bool = False     # int8 KV cache (per-position scales)

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding rows padded to a 128 multiple: TP-shardable (divisible
        by the model axis) and MXU-aligned. Logical ``vocab`` is preserved
        for losses/sampling; the pad rows train toward -inf harmlessly."""
        return -(-self.vocab // 128) * 128

    def param_count(self) -> int:
        d, dh = self.d_model, self.head_dim
        attn = d * dh * (2 * self.n_heads + 2 * self.n_kv_heads)
        if self.moe is not None:
            ffn = (self.moe.n_experts * 3 * d * self.moe.d_ff_expert
                   + d * self.moe.n_experts)
        else:
            ffn = 3 * d * self.d_ff
        per_layer = attn + ffn + 2 * d
        embed = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        pos = self.max_position * d
        return self.n_layers * per_layer + embed + pos + d

    def active_param_count(self) -> int:
        """Per-token active parameters (MoE: top_k experts only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        attn = d * self.head_dim * (2 * self.n_heads + 2 * self.n_kv_heads)
        ffn = self.moe.top_k * 3 * d * self.moe.d_ff_expert
        per_layer = attn + ffn + 2 * d + d * self.moe.n_experts
        embed = self.padded_vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + embed + self.max_position * d + d


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical-axis -> mesh-axis names (None = replicated)."""
    batch: Any = None       # activation batch dim
    heads: Any = None       # attention heads / ffn inner / experts
    kv_seq: Any = None      # KV cache sequence (SP for long decode)
    vocab: Any = None
    dp_size: int = 1        # data-shard count = MoE dispatch group count
    gather_weights: bool = False  # FSDP: all-gather weights in compute dtype

    def c(self, x, spec):
        if all(s is None for s in spec):
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))

    def w(self, weight, dtype):
        """Cast a parameter for compute; under FSDP, constrain the *cast*
        tensor to replicated so the per-layer all-gather moves bf16, not
        the fp32 master shard (halves gather traffic)."""
        weight = weight.astype(dtype)
        if self.gather_weights:
            weight = jax.lax.with_sharding_constraint(
                weight, P(*([None] * weight.ndim)))
        return weight


NO_RULES = Rules()


def quantize_kv(x):
    """Per-(batch, pos, head) int8 quantization: [..., Dh] -> (q, scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    d, dh = cfg.d_model, cfg.head_dim
    h, hkv, L = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    k = iter(jax.random.split(key, 16))
    pt = cfg.param_dtype
    s = lambda *shape: 1.0 / jnp.sqrt(jnp.prod(jnp.array(shape[:-1])) + 1.0)

    def norm_init(*shape):
        return jnp.ones(shape, dtype=pt)

    def dense(key, *shape):
        scale = (2.0 / (shape[-2] + shape[-1])) ** 0.5 if len(shape) >= 2 else 0.02
        return (jax.random.normal(key, shape) * scale).astype(pt)

    params = {
        "embed": dense(next(k), cfg.padded_vocab, d),
        "final_norm": norm_init(d),
        "layers": {
            "attn_norm": norm_init(L, d),
            "ffn_norm": norm_init(L, d),
            "wq": dense(next(k), L, d, h * dh),
            "wk": dense(next(k), L, d, hkv * dh),
            "wv": dense(next(k), L, d, hkv * dh),
            "wo": dense(next(k), L, h * dh, d),
        },
    }
    if cfg.moe is not None:
        e, f = cfg.moe.n_experts, cfg.moe.d_ff_expert
        params["layers"]["router"] = dense(next(k), L, d, e)
        params["layers"]["w_gate"] = dense(next(k), L, e, d, f)
        params["layers"]["w_up"] = dense(next(k), L, e, d, f)
        params["layers"]["w_down"] = dense(next(k), L, e, f, d)
    else:
        params["layers"]["w_gate"] = dense(next(k), L, d, cfg.d_ff)
        params["layers"]["w_up"] = dense(next(k), L, d, cfg.d_ff)
        params["layers"]["w_down"] = dense(next(k), L, cfg.d_ff, d)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), d, cfg.padded_vocab)
    if cfg.max_position:
        params["pos_embed"] = dense(next(k), cfg.max_position, d)
    del s
    return params


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------

def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


def rope(x, positions, theta):
    """x: [B, S, H, Dh]; positions: [B, S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, causal, q_offset, chunk: int = 0):
    """q: [B, Sq, H, Dh]; k, v: [B, Skv, Hkv, Dh] (GQA via reshape).

    ``chunk`` > 0 scans over q blocks (flash-style): scores for one block
    only are ever materialized — O(Sq/chunk) passes, O(B*chunk*Skv) memory
    instead of O(B*Sq*Skv).
    """
    b, sq, h, dh = q.shape
    if chunk and sq > chunk and sq % chunk == 0:
        nc = sq // chunk
        qc = jnp.moveaxis(q.reshape(b, nc, chunk, h, dh), 1, 0)

        def body(carry, args):
            qi, i = args
            off = q_offset + i * chunk
            return carry, _attention(qi, k, v, causal, off)

        _, out = jax.lax.scan(body, None,
                              (qc, jnp.arange(nc, dtype=jnp.int32)))
        return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, dh)
    skv, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    q = q.reshape(b, sq, hkv, group, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(dh).astype(s.dtype)
    if causal:
        q_pos = q_offset[:, None] + jnp.arange(sq)[None, :]   # [B, Sq]
        k_pos = jnp.arange(skv)
        mask = q_pos[:, None, None, :, None] >= k_pos[None, None, None, None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(b, sq, h, dh)


def _moe_ffn(x, router, w_gate, w_up, w_down, moe: MoEConfig, rules: Rules):
    """Token-choice top-k MoE, GShard-style group-wise capacity dispatch.

    Tokens are split into ``rules.dp_size`` groups (= data shards); each
    group routes its local tokens into per-group expert buffers
    ``[G, E, C_local, D]`` sharded (G -> data, E -> model). The expert
    einsums are then fully local per (g, e) pair; the only communication is
    the buf resharding — the intended EP all-to-all — instead of the
    whole-buffer all-reduces a global scatter would induce under GSPMD.
    Group-wise capacity (tokens dropped per group) matches GShard
    semantics; with dp_size=1 it reduces to single-group routing.
    """
    t, d = x.shape
    e, k = moe.n_experts, moe.top_k
    g = max(1, rules.dp_size)
    if t % g != 0:  # tiny decode batches: fall back to fewer groups
        g = 1
        while t % (g * 2) == 0 and g * 2 <= rules.dp_size:
            g *= 2
    tl = t // g
    cap = int(tl * k * moe.capacity_factor / e + 1)
    xg = rules.c(x.reshape(g, tl, d), (rules.batch, None, None))

    def dispatch(xt):
        """One group: [Tl, D] -> buffers + combine metadata."""
        logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype),
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)            # [Tl, K]
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        flat_e = top_e.reshape(-1)                        # [Tl*K]
        order = jnp.argsort(flat_e)                       # stable
        sorted_e = flat_e[order]
        pos_all = jnp.arange(tl * k, dtype=jnp.int32)
        start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
        pos_in_e = pos_all - start[sorted_e]
        keep = pos_in_e < cap
        pos_safe = jnp.where(keep, pos_in_e, 0)
        tok = order // k
        buf = jnp.zeros((e, cap, d), dtype=xt.dtype)
        buf = buf.at[sorted_e, pos_safe].add(
            jnp.where(keep[:, None], xt[tok], 0.0))
        w = top_p.reshape(-1)[order].astype(xt.dtype)
        return buf, (sorted_e, pos_safe, keep, tok, w, probs, top_e)

    buf, info = jax.vmap(dispatch)(xg)                    # [G, E, C, D]
    buf = rules.c(buf, (rules.batch, rules.heads, None, None))
    hg = jnp.einsum("gecd,edf->gecf", buf, rules.w(w_gate, x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    hu = jnp.einsum("gecd,edf->gecf", buf, rules.w(w_up, x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    hidden = jax.nn.silu(hg) * hu
    out_e = jnp.einsum("gecf,efd->gecd", hidden, rules.w(w_down, x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    out_e = rules.c(out_e, (rules.batch, rules.heads, None, None))

    def combine(out_g, inf):
        sorted_e, pos_safe, keep, tok, w, _, _ = inf
        gathered = out_g[sorted_e, pos_safe]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        return jnp.zeros((tl, d), out_g.dtype).at[tok].add(
            gathered * w[:, None])

    y = jax.vmap(combine)(out_e, info).reshape(t, d)
    y = rules.c(y.reshape(g, tl, d), (rules.batch, None, None)).reshape(t, d)
    # load-balancing auxiliary loss (Switch): E * sum(frac_tok * frac_prob)
    probs, top_e = info[5], info[6]
    frac_t = jnp.mean(jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32),
                      (0, 1))
    frac_p = jnp.mean(probs, (0, 1))
    aux = e * jnp.sum(frac_t * frac_p)
    return y, aux


def _dense_ffn(x, w_gate, w_up, w_down, rules: Rules):
    hg = jnp.einsum("td,df->tf", x, rules.w(w_gate, x.dtype))
    hu = jnp.einsum("td,df->tf", x, rules.w(w_up, x.dtype))
    h = jax.nn.silu(hg) * hu
    h = rules.c(h, (rules.batch, rules.heads))
    return jnp.einsum("tf,fd->td", h, rules.w(w_down, x.dtype))


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _layer(cfg: TransformerConfig, rules: Rules, x, lp, positions, cache=None,
           layer_cache=None):
    """One block. x: [B, S, D]. Returns (x, aux, new_kv or None)."""
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = cfg.compute_dtype
    xn = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,de->bse", xn, rules.w(lp["wq"], cd)).reshape(b, s, h, dh)
    k = jnp.einsum("bsd,de->bse", xn, rules.w(lp["wk"], cd)).reshape(b, s, hkv, dh)
    v = jnp.einsum("bsd,de->bse", xn, rules.w(lp["wv"], cd)).reshape(b, s, hkv, dh)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # NOTE: no per-head constraint — head counts need not divide the mesh
    # (phi4: 24 heads on model=16); the flat H*Dh projections carry the
    # sharding and GSPMD propagates through the reshape.
    new_kv = None
    if layer_cache is not None and cfg.kv_quant:
        # int8 KV cache: quantize the fresh K/V slice, store int8+scale,
        # dequantize the full cache for attention. HBM traffic for the
        # cache read drops ~2x (1B + per-row scale vs bf16).
        ck, cv, cks, cvs, cache_len = layer_cache
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, kq, cache_len, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, vq, cache_len, axis=1)
        cks = jax.lax.dynamic_update_slice_in_dim(cks, ks, cache_len, axis=1)
        cvs = jax.lax.dynamic_update_slice_in_dim(cvs, vs, cache_len, axis=1)
        k = dequantize_kv(ck, cks, cd)
        v = dequantize_kv(cv, cvs, cd)
        new_kv = (ck, cv, cks, cvs)
        q_offset = jnp.full((b,), cache_len, jnp.int32)
    elif layer_cache is not None:
        ck, cv, cache_len = layer_cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_len, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_len, axis=1)
        k, v = ck, cv
        new_kv = (ck, cv)
        q_offset = jnp.full((b,), cache_len, jnp.int32)
    else:
        q_offset = jnp.zeros((b,), jnp.int32)
    o = _attention(q, k, v, cfg.causal, q_offset, cfg.attn_chunk)
    o = jnp.einsum("bse,ed->bsd", o.reshape(b, s, h * dh),
                   rules.w(lp["wo"], cd))
    x = x + o
    xn = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    flat = xn.reshape(b * s, d)
    if cfg.moe is not None:
        y, aux = _moe_ffn(flat, lp["router"], lp["w_gate"], lp["w_up"],
                          lp["w_down"], cfg.moe, rules)
    else:
        y = _dense_ffn(flat, lp["w_gate"], lp["w_up"], lp["w_down"], rules)
        aux = jnp.float32(0.0)
    x = x + y.reshape(b, s, d)
    x = rules.c(x, (rules.batch, None, None))
    return x, aux, new_kv


def forward(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            rules: Rules = NO_RULES, cache: dict | None = None,
            cache_len=None):
    """tokens: [B, S]. Returns (hidden [B,S,D], aux_loss, new_cache|None)."""
    cd = cfg.compute_dtype
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
    if cache is not None:
        positions = cache_len + jnp.arange(s)[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (b, s))
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.max_position:
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(cd)
    x = rules.c(x, (rules.batch, None, None))

    lp_stack = params["layers"]

    def scan_body(carry, inputs):
        x, aux = carry
        if cache is None:
            lp = inputs
            x, a, _ = _layer(cfg, rules, x, lp, positions)
            return (x, aux + a), None
        if cfg.kv_quant:
            lp, (ck, cv, cks, cvs) = inputs
            x, a, new_kv = _layer(cfg, rules, x, lp, positions,
                                  layer_cache=(ck, cv, cks, cvs, cache_len))
        else:
            lp, (ck, cv) = inputs
            x, a, new_kv = _layer(cfg, rules, x, lp, positions,
                                  layer_cache=(ck, cv, cache_len))
        return (x, aux + a), new_kv

    body = scan_body
    if cfg.remat and cache is None:
        if cfg.remat_policy == "dots":
            body = jax.checkpoint(scan_body,
                                  policy=jax.checkpoint_policies.dots_saveable)
        else:  # "full": save only layer inputs, recompute the layer in bwd
            body = jax.checkpoint(scan_body)
    if cache is None:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), lp_stack)
        new_cache = None
    else:
        if cfg.kv_quant:
            xs = (lp_stack, (cache["k"], cache["v"], cache["k_scale"],
                             cache["v_scale"]))
        else:
            xs = (lp_stack, (cache["k"], cache["v"]))
        (x, aux), kvs = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
        new_cache = {"k": kvs[0], "v": kvs[1]}
        if cfg.kv_quant:
            new_cache["k_scale"] = kvs[2]
            new_cache["v_scale"] = kvs[3]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, new_cache


def logits_fn(cfg: TransformerConfig, params: dict, hidden: jax.Array,
              rules: Rules = NO_RULES) -> jax.Array:
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    out = jnp.einsum("bsd,dv->bsv", hidden, head.astype(hidden.dtype),
                     preferred_element_type=jnp.float32)
    return rules.c(out, (rules.batch, None, rules.vocab))


def splade_encode(cfg: TransformerConfig, params: dict, tokens: jax.Array,
                  mask: jax.Array, rules: Rules = NO_RULES) -> jax.Array:
    """SPLADE-style learned sparse representation: [B, vocab].

    max-pool over sequence of log(1 + relu(logits)), masked.
    """
    hidden, _, _ = forward(cfg, params, tokens, rules)
    logits = logits_fn(cfg, params, hidden, rules)
    acts = jnp.log1p(jax.nn.relu(logits))
    acts = jnp.where(mask[..., None] > 0, acts, -jnp.inf)
    rep = jnp.max(acts, axis=1)[:, :cfg.vocab]  # drop pad rows
    return jnp.maximum(rep, 0.0)


# --------------------------------------------------------------------------
# steps
# --------------------------------------------------------------------------

def lm_loss(cfg: TransformerConfig, params: dict, batch: dict,
            rules: Rules = NO_RULES):
    hidden, aux, _ = forward(cfg, params, batch["tokens"], rules)
    logits = logits_fn(cfg, params, hidden, rules)
    tgt = batch["targets"]
    # logsumexp - gather: one logits-sized temp instead of a full log_softmax
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = lse - picked
    mask = batch.get("mask", jnp.ones_like(tgt, dtype=jnp.float32))
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + 0.01 * aux


def prefill(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            max_len: int, rules: Rules = NO_RULES):
    """Run prompt, build a KV cache of size max_len. Returns (logits, cache)."""
    b, s = tokens.shape
    hkv, dh, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    kv_dtype = jnp.int8 if cfg.kv_quant else cfg.compute_dtype
    cache = {
        "k": jnp.zeros((L, b, max_len, hkv, dh), kv_dtype),
        "v": jnp.zeros((L, b, max_len, hkv, dh), kv_dtype),
    }
    if cfg.kv_quant:
        cache["k_scale"] = jnp.zeros((L, b, max_len, hkv), jnp.float32)
        cache["v_scale"] = jnp.zeros((L, b, max_len, hkv), jnp.float32)
    cache = jax.tree_util.tree_map(
        lambda c: rules.c(c, (None, rules.batch, rules.kv_seq, None,
                              None)[:c.ndim]),
        cache)
    hidden, _, cache = forward(cfg, params, tokens, rules, cache=cache,
                               cache_len=jnp.int32(0))
    logits = logits_fn(cfg, params, hidden[:, -1:, :], rules)
    return logits, cache


def decode_step(cfg: TransformerConfig, params: dict, token: jax.Array,
                cache: dict, cache_len, rules: Rules = NO_RULES):
    """One decode step. token: [B, 1]. Returns (logits [B,1,V], new cache)."""
    hidden, _, cache = forward(cfg, params, token, rules, cache=cache,
                               cache_len=cache_len)
    logits = logits_fn(cfg, params, hidden, rules)
    return logits, cache
