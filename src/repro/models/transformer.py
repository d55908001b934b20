"""Dense decoder-only LM (qwen2.5 / internlm2 / smollm / qwen1.5-110b) and the
qwen2-vl text backbone (same block; inputs may be precomputed embeddings with
M-RoPE position ids).

Layer params are stacked (leading L axis) and the block is applied with
``lax.scan`` so the HLO stays compact for 80-layer configs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import BATCH, MODEL, shard
from repro.models import attention, common

Array = jax.Array


# ---------------------------------------------------------------------------
# init


def _init_layer(key, cfg: ModelConfig, dtype) -> dict:
    ka, kg, ku, kd = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln1": jnp.ones((d,), dtype),
        "ln2": jnp.ones((d,), dtype),
        "attn": attention.init(ka, cfg, dtype),
        "mlp": {
            "w_gate": common.dense_init(kg, (d, f), dtype=dtype),
            "w_up": common.dense_init(ku, (d, f), dtype=dtype),
            "w_down": common.dense_init(kd, (f, d), scale=1.0 / (2 * cfg.n_layers) ** 0.5, dtype=dtype),
        },
    }


def init(key, cfg: ModelConfig) -> dict:
    dtype = common.dt(cfg.param_dtype)
    ke, kl, kh = jax.random.split(key, 3)
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(jax.random.split(kl, cfg.n_layers))
    params = {
        "embed": common.embed_init(ke, (cfg.padded_vocab, cfg.d_model), dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(kh, (cfg.d_model, cfg.padded_vocab), dtype=dtype)
    return params


def layer_specs(cfg: ModelConfig) -> dict:
    """Compute-time (TP) specs for ONE layer slice (no stacked L axis)."""
    return {
        "ln1": (None,),
        "ln2": (None,),
        "attn": attention.param_specs(cfg),
        "mlp": {"w_gate": (None, MODEL), "w_up": (None, MODEL), "w_down": (MODEL, None)},
    }


def param_specs(cfg: ModelConfig) -> dict:
    """Compute-time (TP) PartitionSpecs, matching the ``init`` tree.

    Layer leaves get a leading ``None`` for the stacked L axis.
    """
    lyr = jax.tree.map(lambda s: (None,) + tuple(s), layer_specs(cfg), is_leaf=lambda s: isinstance(s, tuple))
    specs = {
        "embed": (MODEL, None),
        "layers": lyr,
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = (None, MODEL)
    return specs


# ---------------------------------------------------------------------------
# blocks


def _res(cfg: ModelConfig, h):
    # residual-stream constraint; sp_activations shards the seq dim over the
    # TP axis (Megatron sequence parallelism) so per-layer saved residuals
    # scale as 1/TP — required for the 80-layer 110B cell to fit HBM.
    return shard(h, BATCH, MODEL if cfg.sp_activations else None, None)


def _sp_gather(cfg: ModelConfig, x):
    # explicit Megatron-SP boundary: all-gather the seq-sharded residual
    # before the TP-sharded matmuls. Without this GSPMD resolves the
    # seq<->head sharding clash inside attention by "involuntary full
    # rematerialization" (replicate + repartition) — the dominant collective
    # cost of the 110B baseline.
    if cfg.sp_activations:
        return shard(x, BATCH, None, None)
    return x


@jax.custom_vjp
def _grad_barrier(h):
    return jax.lax.optimization_barrier(h)


def _grad_barrier_fwd(h):
    return jax.lax.optimization_barrier(h), None


def _grad_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


# jax 0.4.x has no differentiation rule for optimization_barrier; the barrier
# is identity-valued, so route gradients through a barrier of their own
# (keeps the hoisting protection on the backward pass too).
_grad_barrier.defvjp(_grad_barrier_fwd, _grad_barrier_bwd)


def _block_train(cfg: ModelConfig, h, layer, positions, mrope_positions, block_k):
    layer = common.constrain_tree(layer, layer_specs(cfg), common.dt(cfg.compute_dtype))  # cast + JIT per-layer gather
    # barrier: stops XLA hoisting the bf16->f32 norm upcast of the saved
    # residual out of the backward loop (which would materialize the WHOLE
    # (L, B, S, D) remat stack in f32 — 2x the largest train buffer)
    h = _grad_barrier(h)
    x = common.rms_norm(h, layer["ln1"], cfg.norm_eps)  # attention is SP-native
    h = h + attention.apply_train(layer["attn"], cfg, x, positions, mrope_positions, block_k=block_k)
    x = _sp_gather(cfg, common.rms_norm(h, layer["ln2"], cfg.norm_eps))
    m = layer["mlp"]
    h = h + common.swiglu(x, m["w_gate"], m["w_up"], m["w_down"])
    return _res(cfg, h)


def _embed_in(params, cfg: ModelConfig, tokens=None, embeds=None):
    if embeds is None:
        w = shard(params["embed"], MODEL, None)  # gather-at-use (pool axis)
        embeds = jnp.take(w, tokens, axis=0)
    h = embeds.astype(common.dt(cfg.compute_dtype))
    return _res(cfg, h)


def _head_w(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return shard(params["embed"], MODEL, None).T
    return shard(params["lm_head"], None, MODEL)


def _logits_out(params, cfg: ModelConfig, h):
    h = common.rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = _head_w(params, cfg)
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype), preferred_element_type=jnp.float32)
    return shard(logits, BATCH, None, MODEL)


def features(
    params: dict,
    cfg: ModelConfig,
    tokens: Optional[Array] = None,
    embeds: Optional[Array] = None,
    positions: Optional[Array] = None,
    mrope_positions: Optional[Array] = None,
    *,
    remat: Optional[bool] = None,
    block_k: Optional[int] = None,
):
    """Trunk -> (post-final-norm h (B,S,D), head weight (D,Vp)).

    The loss path pairs this with ``common.fused_ce_loss`` so the full
    logits tensor is never materialized; ``forward`` keeps the logits API
    for serving and tests.
    """
    block_k = block_k or cfg.attn_block_k
    h = _embed_in(params, cfg, tokens, embeds)
    b, l, _ = h.shape
    if positions is None:
        positions = common.causal_positions(b, l)

    use_remat = cfg.remat if remat is None else remat
    k = max(cfg.remat_every, 1)
    layers = params["layers"]
    if k > 1:
        nl = cfg.n_layers
        assert nl % k == 0, (nl, k)
        layers = jax.tree.map(lambda x: x.reshape(nl // k, k, *x.shape[1:]), layers)

        def block(h, lp):
            # k layers per checkpoint: saved residual stack scales as 1/k,
            # backward recomputes k layers per segment (same total flops
            # as remat_every=1 up to scheduling).
            for i in range(k):
                layer = jax.tree.map(lambda x: x[i], lp)
                h = _block_train(cfg, h, layer, positions, mrope_positions, block_k)
            return h

    else:

        def block(h, layer):
            return _block_train(cfg, h, layer, positions, mrope_positions, block_k)

    block = common.maybe_remat(block, use_remat, cfg.remat_policy)
    h, _ = jax.lax.scan(lambda c, lp: (block(c, lp), None), h, layers)
    h = common.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h, _head_w(params, cfg)


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: Optional[Array] = None,
    embeds: Optional[Array] = None,
    positions: Optional[Array] = None,
    mrope_positions: Optional[Array] = None,
    *,
    remat: Optional[bool] = None,
    block_k: Optional[int] = None,
) -> Array:
    """Full-sequence forward -> logits (B, S, Vp)."""
    h, w = features(
        params, cfg, tokens, embeds, positions, mrope_positions,
        remat=remat, block_k=block_k,
    )
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype), preferred_element_type=jnp.float32)
    return shard(logits, BATCH, None, MODEL)


# ---------------------------------------------------------------------------
# serving


def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: Optional[Array] = None,
    embeds: Optional[Array] = None,
    mrope_positions: Optional[Array] = None,
    *,
    max_len: int,
    block_k: Optional[int] = None,
):
    """Forward + KV cache construction. Returns (logits, cache)."""
    block_k = block_k or cfg.attn_block_k
    h = _embed_in(params, cfg, tokens, embeds)
    b, l, _ = h.shape
    positions = common.causal_positions(b, l)

    def block(h, layer):
        layer = common.constrain_tree(layer, layer_specs(cfg), common.dt(cfg.compute_dtype))
        x = common.rms_norm(h, layer["ln1"], cfg.norm_eps)
        a, (k, v) = attention.apply_prefill(
            layer["attn"], cfg, x, positions, max_len, mrope_positions, block_k=block_k
        )
        h = h + a
        x = common.rms_norm(h, layer["ln2"], cfg.norm_eps)
        m = layer["mlp"]
        h = h + common.swiglu(x, m["w_gate"], m["w_up"], m["w_down"])
        return _res(cfg, h), (k, v)

    h, (ks, vs) = jax.lax.scan(lambda c, lp: block(c, lp), h, params["layers"])
    cache = {
        "k": ks.astype(jnp.bfloat16),
        "v": vs.astype(jnp.bfloat16),
        "lengths": jnp.full((b,), l, jnp.int32),
    }
    return _logits_out(params, cfg, h), cache


def decode_step(params: dict, cfg: ModelConfig, cache: dict, tokens: Array, mrope_positions=None):
    """One decode step. tokens: (B, 1). Returns (logits, cache')."""
    h = _embed_in(params, cfg, tokens)
    lengths = cache["lengths"]

    def step(h, xs):
        layer, kc, vc = xs
        layer = common.constrain_tree(layer, layer_specs(cfg), common.dt(cfg.compute_dtype))
        x = common.rms_norm(h, layer["ln1"], cfg.norm_eps)
        a, kc, vc = attention.apply_decode(layer["attn"], cfg, x, kc, vc, lengths, mrope_positions)
        h = h + a
        x = common.rms_norm(h, layer["ln2"], cfg.norm_eps)
        m = layer["mlp"]
        h = h + common.swiglu(x, m["w_gate"], m["w_up"], m["w_down"])
        return h, (kc, vc)

    h, (ks, vs) = jax.lax.scan(step, h, (params["layers"], cache["k"], cache["v"]))
    logits = _logits_out(params, cfg, h)
    new_cache = {"k": ks, "v": vs, "lengths": lengths + 1}
    return logits, new_cache


def decode_block(
    params: dict, cfg: ModelConfig, cache: dict, tokens: Array, n: Array, out_cols: Array
):
    """C tokens per row in one pass. tokens: (B, C); row i feeds its first
    ``n[i]`` tokens (0 leaves the row's cache and length as they were);
    ``out_cols`` (B,) names the column whose logits each row returns.
    Returns (logits (B, 1, Vp), cache')."""
    h = _embed_in(params, cfg, tokens)
    lengths = cache["lengths"]

    def step(h, xs):
        layer, kc, vc = xs
        layer = common.constrain_tree(layer, layer_specs(cfg), common.dt(cfg.compute_dtype))
        x = common.rms_norm(h, layer["ln1"], cfg.norm_eps)
        a, kc, vc = attention.apply_block(layer["attn"], cfg, x, kc, vc, lengths, n)
        h = h + a
        x = common.rms_norm(h, layer["ln2"], cfg.norm_eps)
        m = layer["mlp"]
        h = h + common.swiglu(x, m["w_gate"], m["w_up"], m["w_down"])
        return h, (kc, vc)

    h, (ks, vs) = jax.lax.scan(step, h, (params["layers"], cache["k"], cache["v"]))
    h = jnp.take_along_axis(h, out_cols[:, None, None], axis=1)  # (B, 1, D)
    logits = _logits_out(params, cfg, h)
    return logits, {"k": ks, "v": vs, "lengths": lengths + n}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return attention.init_cache(cfg, cfg.n_layers, batch, max_len, dtype)


def cache_specs(cfg: ModelConfig, model_axis: int = 16) -> dict:
    return attention.cache_specs(cfg, model_axis)
