"""GQA attention layer: init + train/prefill/decode application.

Layout: projections are stored flat — wq: (D, Hq*hd), wk/wv: (D, Hkv*hd),
wo: (Hq*hd, D) — so TP sharding is a plain column/row split (Megatron style).
KV cache per layer: k/v (B, Hkv, S, hd) + per-sequence lengths (B,).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import BATCH, MODEL, shard
from repro.models import common

Array = jax.Array


def init(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": common.dense_init(ks[0], (d, q_dim), dtype=dtype),
        "wk": common.dense_init(ks[1], (d, kv_dim), dtype=dtype),
        "wv": common.dense_init(ks[2], (d, kv_dim), dtype=dtype),
        "wo": common.dense_init(ks[3], (q_dim, d), scale=1.0 / (2 * cfg.n_layers) ** 0.5, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((q_dim,), dtype)
        p["bk"] = jnp.zeros((kv_dim,), dtype)
        p["bv"] = jnp.zeros((kv_dim,), dtype)
    return p


def param_specs(cfg: ModelConfig) -> dict:
    if cfg.sp_activations:
        # sequence-parallel attention (see _project_qkv): weights replicated
        # over MODEL; the seq dim carries the parallelism end to end, so the
        # attention path has NO resharding at all. Storage still shards over
        # the pool axis (ZeRO), so residency is unchanged.
        p = {"wq": (None, None), "wk": (None, None), "wv": (None, None), "wo": (None, None)}
        if cfg.qkv_bias:
            p.update({"bq": (None,), "bk": (None,), "bv": (None,)})
        return p
    p = {"wq": (None, MODEL), "wk": (None, MODEL), "wv": (None, MODEL), "wo": (MODEL, None)}
    if cfg.qkv_bias:
        p.update({"bq": (MODEL,), "bk": (MODEL,), "bv": (MODEL,)})
    return p


def _project_qkv(p: dict, cfg: ModelConfig, x: Array):
    b, l, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bld,de->ble", x, p["wq"], preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bld,de->ble", x, p["wk"], preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bld,de->ble", x, p["wv"], preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(q.dtype), k + p["bk"].astype(k.dtype), v + p["bv"].astype(v.dtype)
    q = q.reshape(b, l, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, l, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, l, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.sp_activations:
        # context/sequence parallelism: q stays seq-sharded (each shard owns
        # its causal rows), k/v are gathered — tiny for GQA (few kv heads)
        q = shard(q, BATCH, None, MODEL, None)
        k = shard(k, BATCH, None, None, None)
        v = shard(v, BATCH, None, None, None)
    else:
        q = shard(q, BATCH, MODEL, None, None)
        k = shard(k, BATCH, MODEL, None, None)
        v = shard(v, BATCH, MODEL, None, None)
    return q, k, v


def _rope(cfg: ModelConfig, q: Array, k: Array, positions, mrope_positions=None):
    if cfg.rope_theta <= 0:
        return q, k
    if mrope_positions is not None:
        q = common.apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = common.apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _out_proj(p: dict, x_dtype, o: Array) -> Array:
    b, h, l, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, l, h * hd)
    out = jnp.einsum("ble,ed->bld", o, p["wo"], preferred_element_type=jnp.float32)
    return out.astype(x_dtype)


def apply_train(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    positions: Array,
    mrope_positions=None,
    *,
    causal: bool = True,
    block_k: int = 1024,
) -> Array:
    """Full-sequence attention (training / prefill without cache return)."""
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope(cfg, q, k, positions, mrope_positions)
    o = common.attention_chunked(q, k, v, causal=causal, block_k=block_k)
    return _out_proj(p, x.dtype, o)


def apply_prefill(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    positions: Array,
    max_len: int,
    mrope_positions=None,
    block_k: int = 1024,
):
    """As apply_train but also returns the (padded-to-max_len) KV for caching."""
    q, k, v = _project_qkv(p, cfg, x)
    q, k = _rope(cfg, q, k, positions, mrope_positions)
    o = common.attention_chunked(q, k, v, causal=True, block_k=block_k)
    l = x.shape[1]
    if max_len > l:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, max_len - l), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, max_len - l), (0, 0)))
    return _out_proj(p, x.dtype, o), (k, v)


def apply_decode(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    k_cache: Array,
    v_cache: Array,
    lengths: Array,
    mrope_positions=None,
):
    """One-token decode. x: (B, 1, D); caches (B, Hkv, S, hd); lengths (B,).

    Returns (out, k_cache', v_cache'). The new K/V is written at position
    ``lengths`` per sequence; attention sees ``lengths + 1`` valid entries.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)
    positions = lengths[:, None].astype(jnp.int32)  # (B, 1)
    q, k = _rope(cfg, q, k, positions, mrope_positions)
    idx = jnp.arange(b)
    k_cache = k_cache.at[idx, :, lengths, :].set(k[:, :, 0, :].astype(k_cache.dtype))
    v_cache = v_cache.at[idx, :, lengths, :].set(v[:, :, 0, :].astype(v_cache.dtype))
    o = common.attention_decode(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), lengths + 1)
    return _out_proj(p, x.dtype, o), k_cache, v_cache


def apply_block(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    k_cache: Array,
    v_cache: Array,
    lengths: Array,
    n: Array,
):
    """C-token decode. x: (B, C, D); caches (B, Hkv, S, hd); lengths (B,);
    n (B,): row i feeds its first ``n[i]`` columns.

    Column j of row i sits at position ``lengths[i] + j``. The K/V of the
    fed columns are written at ``lengths .. lengths + n - 1``; the other
    columns write nothing, so a row with ``n == 0`` keeps its cache bit for
    bit. Query column j attends causally to the keys at positions
    ``< lengths + j + 1``. Returns (out, k_cache', v_cache').
    """
    c = x.shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    cols = jnp.arange(c, dtype=jnp.int32)
    positions = lengths[:, None].astype(jnp.int32) + cols  # (B, C)
    q, k = _rope(cfg, q, k, positions)
    fed = cols < n[:, None]  # (B, C)
    # the write is a one-hot (S, C) contraction per row and a select, not a
    # scatter: on a TPU the scatter relays each layer's cache out and back
    # (a v5e chunk step at 16 slots of 2048 positions: 57.5 ms scattered,
    # 37.0 ms this way). Unfed columns match no position, so they write
    # nothing, and a row near max_len never shifts a write onto valid keys.
    hit = jnp.arange(k_cache.shape[2])[None, :, None] == jnp.where(fed, positions, -1)[:, None, :]
    written = hit.any(axis=-1)[:, None, :, None]  # (B, 1, S, 1)

    def put(cache, new):
        new = jnp.where(fed[:, None, :, None], new, 0).astype(cache.dtype)
        # one nonzero term per written position, so the f32 sum is exact
        full = jnp.einsum(
            "bsc,bhcd->bhsd", hit.astype(cache.dtype), new, preferred_element_type=jnp.float32
        )
        return jnp.where(written, full.astype(cache.dtype), cache)

    k_cache, v_cache = put(k_cache, k), put(v_cache, v)
    o = common.attention_block(
        q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), positions + 1
    )
    return _out_proj(p, x.dtype, o), k_cache, v_cache


def init_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, model_axis: int = 16) -> dict:
    """Sharding for the stacked cache: heads over MODEL when divisible, else seq."""
    if cfg.n_kv_heads % model_axis == 0:
        kv = (None, BATCH, MODEL, None, None)
    else:
        kv = (None, BATCH, None, MODEL, None)
    return {"k": kv, "v": kv, "lengths": (BATCH,)}
