"""Model assembly: ArchConfig -> init / forward / decode, single-device or
inside the shard_map pipeline (stage.py slices the stacked layer params).

Layer parameters are stacked on a leading ``[L, ...]`` axis and scanned —
this is what lets the pipeline shard contiguous layer ranges over stages and
keeps compiled HLO size O(1) in depth.  Per-layer heterogeneity (gemma3
local/global, MoE first-k-dense, whisper enc/dec) is expressed with
per-layer metadata arrays consumed by the scanned block body.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L

Params = dict


# ---------------------------------------------------------------------------
# Per-layer static metadata (scanned alongside params).
# ---------------------------------------------------------------------------

def layer_meta(cfg: ArchConfig) -> dict:
    n = cfg.n_layers
    is_global = jnp.array([cfg.is_global_layer(i) for i in range(n)])
    theta = jnp.where(is_global,
                      cfg.rope_theta_global or cfg.rope_theta,
                      cfg.rope_theta).astype(jnp.float32)
    is_decoder = jnp.array([i >= cfg.n_enc_layers for i in range(n)]) \
        if cfg.n_enc_layers else jnp.ones((n,), bool)
    is_moe = jnp.array([cfg.moe is not None and i >= cfg.moe.first_k_dense
                        for i in range(n)])
    return dict(is_global=is_global, rope_theta=theta,
                is_decoder=is_decoder, is_moe=is_moe)


# ---------------------------------------------------------------------------
# Single-layer init / apply (family dispatch).
# ---------------------------------------------------------------------------

def init_block(cfg: ArchConfig, key: jax.Array, tp: int, dtype) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Params = {"ln1": L.init_rms_norm(d, dtype)}
    if cfg.family == "ssm":
        p["ssm"] = L.init_ssm(ks[0], cfg, tp, dtype)
        return p
    # attention
    if cfg.attn_kind == "mla":
        p["attn"] = L.init_mla(ks[0], cfg, tp, dtype)
    else:
        p["attn"] = L.init_gqa(ks[0], cfg, tp, dtype)
    if cfg.family == "hybrid":
        p["ssm"] = L.init_ssm(ks[1], cfg, tp, dtype)
    if cfg.n_enc_layers:
        p["ln_x"] = L.init_rms_norm(d, dtype)
        p["xattn"] = L.init_cross(ks[2], cfg, tp, dtype)
    p["ln2"] = L.init_rms_norm(d, dtype)
    if cfg.moe is not None:
        p["moe"] = L.init_moe(ks[3], cfg, tp, dtype)
        if cfg.moe.first_k_dense > 0:
            p["mlp"] = L.init_mlp(ks[4], d, cfg.d_ff, tp, cfg.n_layers, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[4], d, cfg.d_ff, tp, cfg.n_layers, dtype)
    return p


def block_apply(cfg: ArchConfig, p: Params, x, meta_l: dict, *,
                pos, pos3=None, enc=None, cache_l=None,
                tp_axis=None, tp_index=None,
                dp_axis=None, dp_index=None, n_dp=1):
    """Apply one block.  Returns (x', cache_l', aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache_l

    if cfg.family == "audio":
        return _whisper_block(cfg, p, x, meta_l, pos=pos, cache_l=cache_l,
                              tp_axis=tp_axis)

    if cfg.family == "ssm":
        # SSM params are replicated over tensor (never sharded): no psum
        h, new_ssm = L.ssm_block(p["ssm"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                                 cfg, cache=None if cache_l is None else cache_l["ssm"],
                                 tp_axis=None)
        x = x + h
        if cache_l is not None:
            new_cache = dict(cache_l, ssm=new_ssm)
        return x, new_cache, aux

    # --- attention (+ parallel SSM for hybrid) -----------------------------
    xin = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.attn_kind == "mla":
        h_attn, new_kv = L.mla_attention(
            p["attn"], xin, cfg, pos=pos,
            cache=None if cache_l is None else cache_l["kv"], tp_axis=tp_axis)
    else:
        h_attn, new_kv = L.gqa_attention(
            p["attn"], xin, cfg, pos=pos, is_global=meta_l["is_global"],
            rope_theta=meta_l["rope_theta"],
            cache=None if cache_l is None else cache_l["kv"],
            tp_axis=tp_axis, tp_index=tp_index, pos3=pos3)
    if cfg.family == "hybrid":
        h_ssm, new_ssm = L.ssm_block(
            p["ssm"], xin, cfg,
            cache=None if cache_l is None else cache_l["ssm"], tp_axis=None)
        h = 0.5 * (h_attn + h_ssm)          # Hymba: parallel head fusion
    else:
        h, new_ssm = h_attn, None
    x = x + h
    # --- FFN ----------------------------------------------------------------
    xin2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y_moe, aux = L.moe_block(p["moe"], xin2, cfg, cfg.act,
                                 tp_axis=tp_axis, tp_index=tp_index,
                                 dp_axis=dp_axis, dp_index=dp_index,
                                 n_dp=n_dp)
        if cfg.moe.first_k_dense > 0:
            y_dense = L.mlp(p["mlp"], xin2, cfg.act, tp_axis)
            y = jnp.where(meta_l["is_moe"], y_moe, y_dense)
            aux = jnp.where(meta_l["is_moe"], aux, 0.0)
        else:
            y = y_moe
    else:
        y = L.mlp(p["mlp"], xin2, cfg.act, tp_axis)
    x = x + y
    if cache_l is not None:
        new_cache = dict(cache_l)
        if new_kv is not None:
            new_cache["kv"] = new_kv
        if new_ssm is not None:
            new_cache["ssm"] = new_ssm
    return x, new_cache, aux


def _whisper_block(cfg: ArchConfig, p: Params, x, meta_l, *, pos, cache_l,
                   tp_axis):
    """Whisper enc-dec block.  ``x`` is a dict(h_enc, h_dec); encoder layers
    transform h_enc, decoder layers transform h_dec (cross-attending h_enc).
    lax.cond keeps only one path live per layer at runtime."""
    aux = jnp.zeros((), jnp.float32)
    h_enc, h_dec = x["h_enc"], x["h_dec"]
    is_dec = meta_l["is_decoder"]

    if cache_l is not None:
        # decode: only decoder layers do work; encoder layers are identity
        # (their is_decoder flag is False only in the stacked prefix).
        def dec_path(h):
            xin = L.rms_norm(h, p["ln1"], cfg.norm_eps)
            a, new_kv = L.gqa_attention(p["attn"], xin, cfg, pos=pos,
                                        is_global=jnp.array(True),
                                        rope_theta=meta_l["rope_theta"],
                                        cache=cache_l["kv"], tp_axis=tp_axis)
            h = h + a
            xq = L.rms_norm(h, p["ln_x"], cfg.norm_eps)
            hd = cfg.resolved_head_dim
            nh_l = p["xattn"]["wq"].shape[1] // hd
            B, T, _ = xq.shape
            q = (xq @ p["xattn"]["wq"]).reshape(B, T, nh_l, hd)
            o = L.attend(q, cache_l["xk"], cache_l["xv"],
                         scale=1.0 / math.sqrt(hd), causal=False)
            o = o.reshape(B, T, nh_l * hd) @ p["xattn"]["wo"]
            h = h + L._maybe_psum(o, tp_axis)
            h = h + L.mlp(p["mlp"], L.rms_norm(h, p["ln2"], cfg.norm_eps),
                          cfg.act, tp_axis)
            return h, new_kv

        h_new, new_kv = dec_path(h_dec)
        gate = is_dec.astype(h_dec.dtype)
        h_dec = gate * h_new + (1 - gate) * h_dec
        new_cache = dict(cache_l, kv=jax.tree.map(
            lambda a, b: jnp.where(is_dec, a, b) if a.shape == b.shape else a,
            new_kv, cache_l["kv"]))
        return dict(h_enc=h_enc, h_dec=h_dec), new_cache, aux

    def enc_path(args):
        h_enc, h_dec = args
        xin = L.rms_norm(h_enc, p["ln1"], cfg.norm_eps)
        B, S, _ = xin.shape
        hd = cfg.resolved_head_dim
        nh_l = p["attn"]["wq"].shape[1] // hd
        q = (xin @ p["attn"]["wq"]).reshape(B, S, nh_l, hd)
        k = (xin @ p["attn"]["wk"]).reshape(B, S, -1, hd)
        v = (xin @ p["attn"]["wv"]).reshape(B, S, -1, hd)
        o = L.attend(q, k, v, scale=1.0 / math.sqrt(hd), causal=False)
        o = o.reshape(B, S, nh_l * hd) @ p["attn"]["wo"]
        h_enc = h_enc + L._maybe_psum(o, tp_axis)
        h_enc = h_enc + L.mlp(p["mlp"], L.rms_norm(h_enc, p["ln2"], cfg.norm_eps),
                              cfg.act, tp_axis)
        return h_enc, h_dec

    def dec_path(args):
        h_enc, h_dec = args
        xin = L.rms_norm(h_dec, p["ln1"], cfg.norm_eps)
        a, _ = L.gqa_attention(p["attn"], xin, cfg, pos=pos,
                               is_global=jnp.array(True),
                               rope_theta=meta_l["rope_theta"], tp_axis=tp_axis)
        h_dec = h_dec + a
        xq = L.rms_norm(h_dec, p["ln_x"], cfg.norm_eps)
        h_dec = h_dec + L.cross_attention(p["xattn"], xq, h_enc, cfg, tp_axis)
        h_dec = h_dec + L.mlp(p["mlp"], L.rms_norm(h_dec, p["ln2"], cfg.norm_eps),
                              cfg.act, tp_axis)
        return h_enc, h_dec

    h_enc, h_dec = lax.cond(is_dec, dec_path, enc_path, (h_enc, h_dec))
    return dict(h_enc=h_enc, h_dec=h_dec), None, aux


# ---------------------------------------------------------------------------
# Whole-model init.
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key: jax.Array, *, tp: int = 1,
                dtype=jnp.float32) -> Params:
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    vl = cfg.padded_vocab(tp) // tp if tp > 1 else cfg.vocab
    embed = jax.random.normal(k_emb, (vl, cfg.d_model), dtype) \
        / math.sqrt(cfg.d_model)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_block(cfg, k, tp, dtype))(layer_keys)
    p = dict(embed=embed, layers=stacked,
             final_norm=L.init_rms_norm(cfg.d_model, dtype))
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(k_out, (vl, cfg.d_model), dtype) \
            / math.sqrt(cfg.d_model)
    return p


def param_count(p: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(p))


# ---------------------------------------------------------------------------
# Embedding / head with optional vocab sharding over the tensor axis.
# ---------------------------------------------------------------------------

def sinusoid_pos(pos: jax.Array, d: int, dtype) -> jax.Array:
    """[B,T] -> [B,T,d] sinusoidal absolute positions (whisper-style)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / max(1, half - 1))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def embed_tokens(cfg: ArchConfig, table: jax.Array, tokens: jax.Array,
                 tp_axis=None, tp_index=None) -> jax.Array:
    if tp_axis is None:
        x = jnp.take(table, tokens, axis=0)
    else:
        vl = table.shape[0]
        local = tokens - tp_index * vl
        ok = (local >= 0) & (local < vl)
        x = jnp.take(table, jnp.clip(local, 0, vl - 1), axis=0)
        x = jnp.where(ok[..., None], x, 0.0)
        x = lax.psum(x, tp_axis)
    if cfg.arch_id.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def logits_and_xent(cfg: ArchConfig, params: Params, x: jax.Array,
                    labels: jax.Array, tp_axis=None, tp_index=None, *,
                    row0=None, skip=None) -> jax.Array:
    """Mean cross-entropy; supports vocab-sharded head via the standard
    pmax/psum-decomposed softmax (never materialises gathered logits).

    Sharded over ``tp_axis`` (a mesh axis or a tuple of them), the local
    table holds the global vocabulary rows ``row0 + i`` (default
    ``tp_index * rows``); its first ``skip`` rows belong to another shard
    and are masked like the vocabulary pad."""
    table = params.get("head", params["embed"])
    logits = (x @ table.T).astype(jnp.float32)              # [B,T,Vl]
    if tp_axis is None:
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - lab)
    vl = table.shape[0]
    if row0 is None:
        row0 = tp_index * vl
    valid = row0 + jnp.arange(vl) < cfg.vocab                # mask vocab pad
    if skip is not None:
        valid = valid & (jnp.arange(vl) >= skip)
    logits = jnp.where(valid, logits, -1e30)
    # stop_gradient: the subtracted max is a constant shift (no pmax VJP)
    gmax = lax.pmax(lax.stop_gradient(jnp.max(logits, axis=-1)), tp_axis)
    sumexp = lax.psum(jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1),
                      tp_axis)
    lse = gmax + jnp.log(sumexp)
    local = labels - row0
    ok = (local >= (0 if skip is None else skip)) & (local < vl)
    lab = jnp.take_along_axis(logits, jnp.clip(local, 0, vl - 1)[..., None],
                              axis=-1)[..., 0]
    lab = lax.psum(jnp.where(ok, lab, 0.0), tp_axis)
    return jnp.mean(lse - lab)


# ---------------------------------------------------------------------------
# Full forward / loss (single device or per-stage-free path).
# ---------------------------------------------------------------------------

def _scan_layers(cfg: ArchConfig, params: Params, x, meta, *, pos, pos3=None,
                 cache=None, tp_axis=None, tp_index=None):
    def body(carry, inp):
        x, aux = carry
        (lp, ml, cl) = inp
        x, new_cl, a = block_apply(cfg, lp, x, ml, pos=pos, pos3=pos3,
                                   cache_l=cl, tp_axis=tp_axis,
                                   tp_index=tp_index)
        return (x, aux + a), new_cl

    (x, aux), new_cache = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params["layers"], meta, cache))
    return x, aux, new_cache


def forward(cfg: ArchConfig, params: Params, batch: dict, *,
            cache=None, tp_axis=None, tp_index=None):
    """Full forward.  ``batch``: tokens [B,T] (+ pos3 for vlm, frames for
    audio).  Returns (hidden, aux, new_cache)."""
    meta = layer_meta(cfg)
    if cfg.family == "audio":
        h_dec = embed_tokens(cfg, params["embed"], batch["tokens"],
                             tp_axis, tp_index)
        pos = batch.get("pos", _default_pos(batch["tokens"], cache))
        h_dec = h_dec + sinusoid_pos(pos, cfg.d_model, h_dec.dtype)
        if "frames" in batch:
            B, S = batch["frames"].shape[:2]
            enc_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            h_enc = (batch["frames"].astype(h_dec.dtype)
                     + sinusoid_pos(enc_pos, cfg.d_model, h_dec.dtype))
        else:   # decode: cross K/V live in the cache, h_enc is vestigial
            h_enc = jnp.zeros((h_dec.shape[0], 1, cfg.d_model), h_dec.dtype)
        x = dict(h_enc=h_enc, h_dec=h_dec)
    elif "embeds" in batch:                                   # vlm stub frontend
        x = batch["embeds"]
        pos = batch.get("pos", _default_pos_from_x(x, cache))
    else:
        x = embed_tokens(cfg, params["embed"], batch["tokens"],
                         tp_axis, tp_index)
        pos = batch.get("pos", _default_pos(batch["tokens"], cache))
    pos3 = batch.get("pos3")
    x, aux, new_cache = _scan_layers(cfg, params, x, meta, pos=pos, pos3=pos3,
                                     cache=cache, tp_axis=tp_axis,
                                     tp_index=tp_index)
    if cfg.family == "audio":
        x = x["h_dec"]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, new_cache


def _default_pos(tokens, cache):
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    if cache is not None:
        off = _cache_len(cache)
        pos = pos + (off[:, None] if getattr(off, "ndim", 0) else off)
    return pos


def _default_pos_from_x(x, cache):
    B, T = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    if cache is not None:
        off = _cache_len(cache)
        pos = pos + (off[:, None] if getattr(off, "ndim", 0) else off)
    return pos


def _cache_len(cache):
    # cache["kv"]["len"] is stacked [L, B] — per-layer, per-slot offsets
    # (every request row sits at its own sequence position).  Encoder
    # layers never advance theirs (whisper), so reduce layer-like leading
    # axes with max and keep the per-slot [B] vector.
    if isinstance(cache, dict) and "kv" in cache and "len" in cache["kv"]:
        l = cache["kv"]["len"]
        if l.ndim <= 1:
            return jnp.max(l) if l.ndim else l
        return jnp.max(l, axis=tuple(range(l.ndim - 1)))
    return 0


def loss_fn(cfg: ArchConfig, params: Params, batch: dict, *,
            tp_axis=None, tp_index=None) -> jax.Array:
    x, aux, _ = forward(cfg, params, batch, tp_axis=tp_axis, tp_index=tp_index)
    ce = logits_and_xent(cfg, params, x, batch["labels"], tp_axis, tp_index)
    return ce + aux


# ---------------------------------------------------------------------------
# Decode caches.
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int, *, tp: int = 1,
               dtype=jnp.float32, enc_len: int = 0) -> dict:
    """Stacked [L, ...] decode cache for every layer."""
    n = cfg.n_layers
    hd = cfg.resolved_head_dim
    nkv = max(1, cfg.n_kv_heads // tp)
    c: dict = {}
    if cfg.family == "ssm":
        c["ssm"] = _ssm_cache(cfg, n, batch, tp, dtype)
        return c
    if cfg.attn_kind == "mla":
        m = cfg.mla
        c["kv"] = dict(
            c_kv=jnp.zeros((n, batch, max_len, m.kv_lora_rank), dtype),
            k_rope=jnp.zeros((n, batch, max_len, m.qk_rope_dim), dtype),
            len=jnp.zeros((n, batch), jnp.int32))
    else:
        c["kv"] = dict(k=jnp.zeros((n, batch, max_len, nkv, hd), dtype),
                       v=jnp.zeros((n, batch, max_len, nkv, hd), dtype),
                       len=jnp.zeros((n, batch), jnp.int32))
    if cfg.family == "hybrid":
        c["ssm"] = _ssm_cache(cfg, n, batch, tp, dtype)
    if cfg.n_enc_layers:
        nh_l = cfg.n_heads // tp
        c["xk"] = jnp.zeros((n, batch, enc_len, nh_l, hd), dtype)
        c["xv"] = jnp.zeros((n, batch, enc_len, nh_l, hd), dtype)
    return c


def _ssm_cache(cfg: ArchConfig, n: int, batch: int, tp: int, dtype) -> dict:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model // tp
    nh = max(1, s.n_heads(cfg.d_model) // tp)
    conv_ch = d_inner + 2 * s.d_state
    return dict(conv=jnp.zeros((n, batch, s.d_conv - 1, conv_ch), dtype),
                state=jnp.zeros((n, batch, nh, s.head_dim, s.d_state), dtype))


def prefill_audio_cache(cfg: ArchConfig, params: Params, frames: jax.Array,
                        cache: dict, *, tp_axis=None) -> dict:
    """Whisper serving: run the encoder stack once and fill the per-layer
    cross-attention K/V cache consumed by every decode step."""
    B, S = frames.shape[:2]
    meta = layer_meta(cfg)
    enc_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = frames + sinusoid_pos(enc_pos, cfg.d_model, frames.dtype)
    x = dict(h_enc=h, h_dec=jnp.zeros((B, 1, cfg.d_model), frames.dtype))
    pos = jnp.zeros((B, 1), jnp.int32)
    x, _, _ = _scan_layers(cfg, params, x, meta, pos=pos, tp_axis=tp_axis)
    enc_out = x["h_enc"]
    hd = cfg.resolved_head_dim

    def per_layer(lp):
        nkv = lp["xattn"]["wk"].shape[1] // hd
        xk = (enc_out @ lp["xattn"]["wk"]).reshape(B, S, nkv, hd)
        xv = (enc_out @ lp["xattn"]["wv"]).reshape(B, S, nkv, hd)
        return xk, xv

    xk, xv = jax.vmap(per_layer)(params["layers"])
    return dict(cache, xk=xk.astype(cache["xk"].dtype),
                xv=xv.astype(cache["xv"].dtype))


def decode_step(cfg: ArchConfig, params: Params, batch: dict, cache: dict,
                *, tp_axis=None, tp_index=None):
    """One-token decode: batch['tokens'] is [B,1].  Returns (logits-hidden,
    new_cache)."""
    x, _, new_cache = forward(cfg, params, batch, cache=cache,
                              tp_axis=tp_axis, tp_index=tp_index)
    table = params.get("head", params["embed"])
    logits = (x @ table.T).astype(jnp.float32)
    return logits, new_cache
