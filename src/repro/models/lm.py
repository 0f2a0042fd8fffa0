"""Model assembly for every assigned architecture family + train/serve steps.

Families: dense | moe | ssm | hybrid | encdec | vlm   (configs/base.py).
Layer stacks are scanned (`lax.scan`) with per-layer remat; activations can
be sequence-sharded between layers (SP).  The embedding / output head is
vocab-sharded ("model" axis) — logits stay vocab-sharded so the softmax
all-reduces only [B,S] statistics (see sharding.py).

The paper's technique appears here as `lsh_softmax`: simLSH candidate
sampling over the output-embedding rows replaces the full-vocab softmax
(DESIGN.md §4) — the same "avoid the O(N) object" move as LSH-MF itself.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

# --------------------------------------------------------------------------
# parameter initialization (pure; dry-run uses jax.eval_shape over this)
# --------------------------------------------------------------------------


def _dense_layer_init(cfg: ArchConfig, key, scale):
    hd, D, ff = cfg.hd, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 8)
    pd = cfg.param_dtype
    p = dict(
        ln1=jnp.ones((D,), pd),
        ln2=jnp.ones((D,), pd),
        wq=scale * jax.random.normal(ks[0], (D, cfg.n_heads_padded, hd), pd),
        wk=scale * jax.random.normal(ks[1], (D, cfg.n_kv, hd), pd),
        wv=scale * jax.random.normal(ks[2], (D, cfg.n_kv, hd), pd),
        wo=scale * jax.random.normal(ks[3], (cfg.n_heads_padded, hd, D), pd),
    )
    if cfg.qkv_bias:
        p |= dict(bq=jnp.zeros((cfg.n_heads_padded, hd), pd),
                  bk=jnp.zeros((cfg.n_kv, hd), pd),
                  bv=jnp.zeros((cfg.n_kv, hd), pd))
    if cfg.qk_norm:
        p |= dict(q_norm=jnp.ones((hd,), pd), k_norm=jnp.ones((hd,), pd))
    if cfg.family == "moe" and cfg.n_experts:
        E = cfg.n_experts
        p |= dict(
            router=scale * jax.random.normal(ks[4], (D, E), pd),
            w1=scale * jax.random.normal(ks[5], (E, D, ff), pd),
            w3=scale * jax.random.normal(ks[6], (E, D, ff), pd),
            w2=scale * jax.random.normal(ks[7], (E, ff, D), pd),
        )
        if cfg.moe_dense_ff:
            fd = cfg.moe_dense_ff
            p |= dict(
                w1d=scale * jax.random.normal(jax.random.fold_in(key, 11), (D, fd), pd),
                w3d=scale * jax.random.normal(jax.random.fold_in(key, 12), (D, fd), pd),
                w2d=scale * jax.random.normal(jax.random.fold_in(key, 13), (fd, D), pd),
            )
    else:
        p |= dict(
            w1=scale * jax.random.normal(ks[5], (D, ff), pd),
            w3=scale * jax.random.normal(ks[6], (D, ff), pd),
            w2=scale * jax.random.normal(ks[7], (ff, D), pd),
        )
    return p


def _ssm_layer_init(cfg: ArchConfig, key, scale):
    D, di, N = cfg.d_model, SSM.d_inner(cfg), cfg.ssm_state
    H, K = SSM.n_heads(cfg), cfg.ssm_conv
    ks = jax.random.split(key, 10)
    pd = cfg.param_dtype
    return dict(
        ln=jnp.ones((D,), pd),
        z_proj=scale * jax.random.normal(ks[0], (D, di), pd),
        x_proj=scale * jax.random.normal(ks[1], (D, di), pd),
        b_proj=scale * jax.random.normal(ks[2], (D, N), pd),
        c_proj=scale * jax.random.normal(ks[3], (D, N), pd),
        dt_proj=scale * jax.random.normal(ks[4], (D, H), pd),
        conv_x=scale * jax.random.normal(ks[5], (K, di), pd),
        conv_b=scale * jax.random.normal(ks[6], (K, N), pd),
        conv_c=scale * jax.random.normal(ks[7], (K, N), pd),
        dt_bias=jnp.zeros((H,), pd),
        A_log=jnp.zeros((H,), pd),
        D=jnp.ones((H,), pd),
        norm_w=jnp.ones((di,), pd),
        out_proj=scale * jax.random.normal(ks[8], (di, D), pd),
    )


def _stack_init(per_layer_fn, cfg, key, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: per_layer_fn(cfg, k, 0.02))(keys)


def init_params(cfg: ArchConfig, key, model_shards: int = 16):
    ks = jax.random.split(key, 6)
    pd = cfg.param_dtype
    V = cfg.vocab_padded(model_shards)
    D = cfg.d_model
    p = dict(
        embed=0.02 * jax.random.normal(ks[0], (V, D), pd),
        final_norm=jnp.ones((D,), pd),
    )
    if not cfg.tie_embeddings:
        p["out_embed"] = 0.02 * jax.random.normal(ks[1], (V, D), pd)

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        p["layers"] = _stack_init(_dense_layer_init, cfg, ks[2], cfg.L)
    elif fam == "ssm":
        p["layers"] = _stack_init(_ssm_layer_init, cfg, ks[2], cfg.L)
    elif fam == "hybrid":
        p["layers"] = _stack_init(_ssm_layer_init, cfg, ks[2], cfg.L)
        dense_cfg = dataclasses.replace(cfg, family="dense")
        p["shared_attn"] = _dense_layer_init(dense_cfg, ks[3], 0.02)
    elif fam == "encdec":
        p["enc"] = _stack_init(_dense_layer_init, cfg, ks[2], cfg.enc_layers)
        p["dec"] = _stack_init(_dense_layer_init, cfg, ks[3], cfg.L)
        # cross-attention stack for the decoder
        dec_x = _stack_init(_dense_layer_init, cfg, ks[4], cfg.L)
        keys = {"ln1", "wq", "wk", "wv", "wo"}
        if cfg.qkv_bias:
            keys |= {"bq", "bk", "bv"}
        if cfg.qk_norm:
            keys |= {"q_norm", "k_norm"}
        p["dec_cross"] = {k: dec_x[k] for k in keys}
        p["enc_norm"] = jnp.ones((D,), pd)
    else:
        raise ValueError(fam)
    return p


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------


def _attn_sublayer(pl, x, cfg, *, causal, q_offset=0, window=0,
                   kv_cache=None, cache_pos=None, kv_override=None,
                   mesh_axes=None):
    """Attention residual sub-layer.

    Returns (x', info) with info["kv"] = this block's (roped) K/V — what a
    prefill writes to the cache — and info["cache"] = the updated full
    cache when one was passed in (decode).
    """
    xn = L.rms_norm(x, pl["ln1"], cfg.norm_eps)
    xn = L.gather_seq(xn, cfg, mesh_axes)
    q, k, v = L.qkv_proj(pl, xn, cfg)
    S = xn.shape[1]
    if (mesh_axes and cfg.n_heads_padded % mesh_axes["ntp"] != 0 and S > 1):
        # ring-attention layout: queries sequence-sharded over tp, K/V
        # replicated over tp (all-gathered) — used when the head count
        # (arctic: 56) does not divide the model axis
        dp, tp = mesh_axes["dp"], mesh_axes["tp"]
        q = jax.lax.with_sharding_constraint(q, P(dp, tp, None, None))
        k = jax.lax.with_sharding_constraint(k, P(dp, None, None, None))
        v = jax.lax.with_sharding_constraint(v, P(dp, None, None, None))
    if kv_override is not None:                      # cross-attention
        k, v = kv_override
    else:
        pos = q_offset + jnp.arange(S)
        q = L.rope(q, pos, cfg.rope_theta)
        k = L.rope(k, pos, cfg.rope_theta)
    info = {"kv": (k, v), "cache": None}
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, 1)
        k, v = ck.astype(x.dtype), cv.astype(x.dtype)
        info["cache"] = (ck, cv)
    o = L.attention(q, k, v, q_offset=q_offset, causal=causal,
                    query_chunk=cfg.query_chunk, window=window)
    out = L.scatter_seq(L.attn_out(pl, o, x.dtype), cfg, mesh_axes)
    return x + out, info


def _ffn_sublayer(pl, x, cfg, mesh, mesh_axes, shard_seq=True):
    xn = L.rms_norm(x, pl["ln2"], cfg.norm_eps)
    if cfg.family == "moe" and cfg.n_experts:
        eid, gate = MOE.router(pl, xn, cfg)
        if mesh is None:    # smoke-test path: dense fallback semantics
            y = MOE.moe_dense_ref(pl, xn, eid, gate, cfg)
        elif cfg.moe_ep2d and shard_seq:
            y = MOE.moe_ffn_ep2d(pl, xn, eid, gate, cfg, mesh, mesh_axes,
                                 capacity_factor=cfg.moe_capacity)
        else:
            y = MOE.moe_ffn(pl, xn, eid, gate, cfg, mesh, mesh_axes,
                            capacity_factor=cfg.moe_capacity,
                            shard_seq=shard_seq)
        if cfg.moe_dense_ff:
            xg = L.gather_seq(xn, cfg, mesh_axes)
            y = y + L.scatter_seq(
                L.mlp(dict(w1=pl["w1d"], w3=pl["w3d"], w2=pl["w2d"]), xg),
                cfg, mesh_axes)
        return x + y
    xg = L.gather_seq(xn, cfg, mesh_axes)
    return x + L.scatter_seq(L.mlp(pl, x=xg), cfg, mesh_axes)


def _dense_block(pl, x, cfg, mesh, mesh_axes, *, causal=True, q_offset=0,
                 window=0, kv_cache=None, cache_pos=None, shard_seq=True):
    x, info = _attn_sublayer(pl, x, cfg, causal=causal, q_offset=q_offset,
                             window=window, kv_cache=kv_cache,
                             cache_pos=cache_pos, mesh_axes=mesh_axes)
    x = _ffn_sublayer(pl, x, cfg, mesh, mesh_axes, shard_seq=shard_seq)
    return x, info


def _scan_layers(body, x, stacked, cfg: ArchConfig, mesh_axes):
    """remat'd scan over a stacked layer dict; body(pl, x) → x.

    cfg.unroll_layers uses a python loop instead — identical math, used by
    the roofline extractor because XLA's cost_analysis does not multiply
    scan-body cost by trip count (DESIGN.md §6)."""

    def step(carry, pl):
        if cfg.fsdp:
            # pin the FSDP all-gather of this layer's weights inside the
            # loop body — without the barrier XLA hoists gather-of-slice
            # into slice-of-(gather-of-all-layers): +40 GiB/device at 405B.
            pl = jax.lax.optimization_barrier(pl)
        carry = jax.lax.optimization_barrier(carry)  # save carry @ bf16
        y = body(pl, carry)
        y = L.shard_acts(y, cfg, mesh_axes) if mesh_axes else y
        return y, None

    if cfg.remat:
        step = jax.checkpoint(step)
    if cfg.unroll_layers:
        n = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(n):
            x, _ = step(x, jax.tree.map(lambda a: a[i], stacked))
        return x
    x, _ = jax.lax.scan(step, x, stacked)
    return x


def shard_vocab(x, mesh_axes):
    """Pin a [..., V] tensor to the table's vocab sharding — without this
    XLA may all-gather the 8 GiB table instead (measured at 405B)."""
    if mesh_axes and x.ndim >= 2:
        return jax.lax.with_sharding_constraint(
            x, P(*([mesh_axes["dp"]] + [None] * (x.ndim - 2) + [mesh_axes["tp"]])))
    return x


def embed_tokens(p, cfg, tokens, mesh_axes=None, one_hot=True):
    """Vocab-sharded lookup via one-hot matmul: the one-hot is sharded like
    the table's vocab dim, so the lookup is a local partial matmul + a
    [B,S,D] all-reduce — never a de-shard of the 8 GiB table."""
    if not one_hot:
        return p["embed"][tokens].astype(cfg.dtype)
    V = p["embed"].shape[0]
    oh = shard_vocab(jax.nn.one_hot(tokens, V, dtype=cfg.dtype), mesh_axes)
    return jnp.einsum("bsv,vd->bsd", oh, p["embed"].astype(cfg.dtype))


def out_embedding(p, cfg):
    return p["embed"] if cfg.tie_embeddings else p["out_embed"]


def forward(cfg: ArchConfig, p, batch, mesh=None, mesh_axes=None):
    """Token/embedding inputs → final hidden states [B, S, D] (normed)."""
    fam = cfg.family
    if fam in ("vlm",) or cfg.frontend == "embed_stub" and fam != "encdec":
        # stub frontend: precomputed patch/frame embeddings are prepended
        x = embed_tokens(p, cfg, batch["tokens"], mesh_axes)
        if "frontend_embeds" in batch:
            fe = batch["frontend_embeds"].astype(cfg.dtype)
            x = jnp.concatenate([fe, x], axis=1)
    elif fam == "encdec":
        return _forward_encdec(cfg, p, batch, mesh, mesh_axes)
    else:
        x = embed_tokens(p, cfg, batch["tokens"], mesh_axes)

    if fam in ("dense", "moe", "vlm"):
        body = lambda pl, h: _dense_block(pl, h, cfg, mesh, mesh_axes)[0]
        x = _scan_layers(body, x, p["layers"], cfg, mesh_axes)
    elif fam == "ssm":
        def body(pl, h):
            xn = L.gather_seq(L.rms_norm(h, pl["ln"], cfg.norm_eps),
                              cfg, mesh_axes)
            y = SSM.mamba_block(pl, xn, cfg)[0]
            return h + L.scatter_seq(y, cfg, mesh_axes)
        x = _scan_layers(body, x, p["layers"], cfg, mesh_axes)
    elif fam == "hybrid":
        x = _forward_hybrid(cfg, p, x, mesh, mesh_axes)
    return L.rms_norm(x, p["final_norm"], cfg.norm_eps)


def _hybrid_groups(cfg: ArchConfig):
    """[(start, size), ...] — shared attn block runs before each group."""
    k = cfg.attn_every
    out, s = [], 0
    while s < cfg.L:
        out.append((s, min(k, cfg.L - s)))
        s += k
    return out


def _forward_hybrid(cfg, p, x, mesh, mesh_axes, window=0):
    def body(pl, h):
        xn = L.gather_seq(L.rms_norm(h, pl["ln"], cfg.norm_eps),
                          cfg, mesh_axes)
        y = SSM.mamba_block(pl, xn, cfg)[0]
        return h + L.scatter_seq(y, cfg, mesh_axes)
    for (start, size) in _hybrid_groups(cfg):
        x, _ = _dense_block(p["shared_attn"], x, cfg, mesh, mesh_axes,
                            causal=True, window=window)
        stacked = jax.tree.map(lambda a: jax.lax.slice_in_dim(a, start, start + size),
                               p["layers"])
        x = _scan_layers(body, x, stacked, cfg, mesh_axes)
    return x


def _forward_encdec(cfg, p, batch, mesh, mesh_axes):
    # encoder: frontend embeddings in, bidirectional
    xe = batch["frontend_embeds"].astype(cfg.dtype)
    enc_body = lambda pl, h: _dense_block(pl, h, cfg, mesh, mesh_axes,
                                          causal=False)[0]
    xe = _scan_layers(enc_body, xe, p["enc"], cfg, mesh_axes)
    xe = L.rms_norm(xe, p["enc_norm"], cfg.norm_eps)

    # decoder: self-attn (causal) + cross-attn + mlp, scanned
    xd = embed_tokens(p, cfg, batch["tokens"], mesh_axes)

    def dec_body(pl_pair, h):
        pl, plx = pl_pair
        h, _info = _attn_sublayer(pl, h, cfg, causal=True,
                                  mesh_axes=mesh_axes)
        # cross-attention: KV from encoder output
        xn = L.rms_norm(h, plx["ln1"], cfg.norm_eps)
        q, _, _ = L.qkv_proj(plx, xn, cfg)
        k = jnp.einsum("bsd,dhk->bshk", xe, plx["wk"].astype(xe.dtype))
        v = jnp.einsum("bsd,dhk->bshk", xe, plx["wv"].astype(xe.dtype))
        o = L.attention(q, k, v, q_offset=0, causal=False,
                        query_chunk=cfg.query_chunk)
        h = h + L.attn_out(plx, o, h.dtype)
        return _ffn_sublayer(pl, h, cfg, mesh, mesh_axes)

    def step(carry, pls):
        y = dec_body(pls, carry)
        y = L.shard_acts(y, cfg, mesh_axes) if mesh_axes else y
        return y, None

    if cfg.remat:
        step = jax.checkpoint(step)
    if cfg.unroll_layers:
        for i in range(cfg.L):
            xd, _ = step(xd, jax.tree.map(lambda a: a[i],
                                          (p["dec"], p["dec_cross"])))
        return L.rms_norm(xd, p["final_norm"], cfg.norm_eps)
    xd, _ = jax.lax.scan(step, xd, (p["dec"], p["dec_cross"]))
    return L.rms_norm(xd, p["final_norm"], cfg.norm_eps)
