"""Shared model primitives (pure JAX).

Everything here is written to lower cleanly under GSPMD on big meshes:
 * attention is chunked (lax.scan over KV blocks, online softmax, f32
   accumulators) so prefill at 32k never materializes an (Lq, Lk) matrix;
 * decode (Lq == 1) uses a direct masked einsum so a sequence-sharded KV
   cache partitions without per-iteration gathers;
 * all matmuls request f32 accumulation via preferred_element_type.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# ---------------------------------------------------------------------------
# dtype helpers


def dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# initializers


def dense_init(key, shape, in_axis: int = 0, scale: float = 1.0, dtype=jnp.float32):
    """Truncated-normal fan-in init."""
    fan_in = shape[in_axis]
    std = scale / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms


def rms_norm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, weight: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings


def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _rotate_half(x: Array) -> Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, H, L, D); positions: (B, L) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,L,D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    return (x.astype(jnp.float32) * cos + _rotate_half(x.astype(jnp.float32)) * sin).astype(x.dtype)


def apply_mrope(x: Array, positions: Array, theta: float, sections) -> Array:
    """Qwen2-VL multimodal RoPE. positions: (3, B, L) [t,h,w]; sections sum to D/2."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    # per-frequency section id: first sections[0] freqs use t, next use h, then w
    sec = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (D/2,)
    pos_sel = positions.astype(jnp.float32)[sec].transpose(1, 2, 0)  # (B, L, D/2)
    ang = pos_sel[:, None, :, :] * freqs  # (B,1,L,D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    return (x.astype(jnp.float32) * cos + _rotate_half(x.astype(jnp.float32)) * sin).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (jnp oracle — also the reference for kernels/flash_attention)

NEG_INF = -1e30


def _expand_gqa(q: Array, n_kv: int) -> Array:
    """(B, Hq, L, D) -> (B, Hkv, G, L, D)."""
    b, hq, l, d = q.shape
    return q.reshape(b, n_kv, hq // n_kv, l, d)


def _kv_blocks(k: Array, v: Array, block_k: int):
    """(B,Hkv,Lk,D) k/v -> (nb,B,Hkv,block,D) stacks, zero-padded."""
    b, hkv, lk, d = k.shape
    nb = max(1, -(-lk // block_k))
    pad = nb * block_k - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, hkv, nb, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nb, block_k, d).transpose(2, 0, 1, 3, 4)
    return kb, vb, nb


def _block_scores(qg, kblk, iblk, *, scale, block_k, lk, lq, q_offset, causal, bidirectional):
    """Masked f32 scores for one k-block: (B,Hkv,G,Lq,block).

    Masking is an additive (Lq, block) bias instead of a broadcast ``where``
    over the full score shape: XLA hoists loop-invariant mask tensors out of
    the scan, and a stacked (nb, B, H, G, Lq, block) pred buffer was the
    single largest allocation of the train step. The small bias stack is
    negligible and fuses into the score add.
    """
    kv_pos = iblk * block_k + jnp.arange(block_k)  # (block,)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kblk, preferred_element_type=jnp.float32) * scale
    valid = kv_pos < lk
    if causal and not bidirectional:
        q_pos = q_offset + jnp.arange(lq)
        valid = valid[None, :] & (kv_pos[None, :] <= q_pos[:, None])  # (Lq, block)
        bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
        s = s + bias[None, None, None]
    else:
        bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)  # (block,)
        s = s + bias[None, None, None, None]
    return s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_core(q, k, v, causal, q_offset, block_k, bidirectional):
    out, _ = _attention_fwd_impl(q, k, v, causal, q_offset, block_k, bidirectional)
    return out


def _attention_fwd_impl(q, k, v, causal, q_offset, block_k, bidirectional):
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    qg = _expand_gqa(q, hkv)  # (B,Hkv,G,Lq,D)
    g = qg.shape[2]
    kb, vb, nb = _kv_blocks(k, v, block_k)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, iblk = blk
        s = _block_scores(
            qg, kblk, iblk, scale=scale, block_k=block_k, lk=lk, lq=lq,
            q_offset=q_offset, causal=causal, bidirectional=bidirectional,
        )
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk, preferred_element_type=jnp.float32
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, lq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, lq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,Hkv,G,Lq,D) f32
    # flash-style softmax stats: lse = m + log(l); 0 for fully-masked rows so
    # the backward's exp(s - lse) stays 0 (s is NEG_INF there) instead of nan
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)
    return out.reshape(b, hq, lq, d).astype(q.dtype), lse


def _attention_fwd(q, k, v, causal, q_offset, block_k, bidirectional):
    out, lse = _attention_fwd_impl(q, k, v, causal, q_offset, block_k, bidirectional)
    return out, (q, k, v, out, lse)


def _attention_bwd(causal, q_offset, block_k, bidirectional, res, dout):
    """Flash-attention backward: recompute p per k-block from (q,k,lse).

    Saves only (q,k,v,out,lse) — no stacked per-block score/prob/acc
    residuals, which is what makes the train cells fit per-chip HBM (and it
    mirrors the Pallas kernel's dataflow, HBM traffic = q/k/v/o + grads).
    """
    q, k, v, out, lse = res
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    qg = _expand_gqa(q, hkv)
    g = qg.shape[2]
    kb, vb, nb = _kv_blocks(k, v, block_k)
    do = _expand_gqa(dout, hkv)  # (B,Hkv,G,Lq,D), compute dtype
    og = _expand_gqa(out, hkv)
    delta = (do.astype(jnp.float32) * og.astype(jnp.float32)).sum(-1)  # (B,Hkv,G,Lq)

    def body(dq, blk):
        kblk, vblk, iblk = blk
        s = _block_scores(
            qg, kblk, iblk, scale=scale, block_k=block_k, lk=lk, lq=lq,
            q_offset=q_offset, causal=causal, bidirectional=bidirectional,
        )
        p = jnp.exp(s - lse[..., None])  # exact probs (B,Hkv,G,Lq,block)
        # matmul inputs in compute dtype (as the Pallas kernel does on MXU);
        # accumulation stays f32 via preferred_element_type
        pc = p.astype(v.dtype)
        dv_blk = jnp.einsum("bhgqk,bhgqd->bhkd", pc, do, preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", do, vblk, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(k.dtype)
        dq = dq + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kblk, preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qg, preferred_element_type=jnp.float32)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, hkv, g, lq, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nb)))
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(b, hkv, nb * block_k, d)[:, :, :lk]
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(b, hkv, nb * block_k, d)[:, :, :lk]
    return (
        dq.reshape(b, hq, lq, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


_attention_core.defvjp(_attention_fwd, _attention_bwd)


def attention_chunked(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    block_k: int = 1024,
    bidirectional: bool = False,
) -> Array:
    """Online-softmax attention, O(L * block_k) memory, flash-style custom
    VJP (backward recomputes per-block probs from the saved LSE).

    q: (B, Hq, Lq, D); k, v: (B, Hkv, Lk, D). GQA via Hq % Hkv == 0.
    Returns (B, Hq, Lq, D) in q.dtype.

    The named_scope tags every HLO op of this region so the dry-run cost
    model can attribute its HBM traffic: on TPU this whole region runs as
    the Pallas flash kernel (scores/probs stay in VMEM), so the roofline
    reports both the raw-HLO memory term and the kernel-adjusted one.
    """
    with jax.named_scope("flash_attention_ref"):
        return _attention_core(q, k, v, causal, q_offset, block_k, bidirectional)


def attention_decode(
    q: Array,
    k: Array,
    v: Array,
    kv_length,
    *,
    sink_cache: bool = False,
) -> Array:
    """Single-position attention over a (possibly partially filled) cache.

    q: (B, Hq, 1, D); k, v: (B, Hkv, S, D); kv_length: scalar or (B,) valid len.
    Direct masked einsum — partitions cleanly when S (or Hkv) is sharded.
    """
    b, hq, lq, d = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    qg = _expand_gqa(q, hkv)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32) * scale
    kv_length = jnp.asarray(kv_length)
    if kv_length.ndim == 0:
        kv_length = jnp.broadcast_to(kv_length, (b,))
    mask = jnp.arange(s_len)[None, :] < kv_length[:, None]  # (B, S)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o = o / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return o.reshape(b, hq, lq, d).astype(q.dtype)


CACHE_BLOCK_K = 512  # key positions per online-softmax step of attention_block


def attention_block(q: Array, k: Array, v: Array, kv_length: Array) -> Array:
    """Attention of C query columns per row over a cache, each column with
    its own valid length: the multi-token form of ``attention_decode``.

    q: (B, Hq, C, D); k, v: (B, Hkv, S, D); kv_length: (B, C) — query
    column j of row i sees the keys at positions < kv_length[i, j].

    Online softmax over key blocks of ``CACHE_BLOCK_K`` positions (one block
    when that does not divide S), sliced from the cache in place, so only one
    block's f32 scores are live. On a TPU v5e, smollm-360m's chunk step at
    16 slots of 2048 positions and 128 columns took 57.5 ms with this form
    and 79.5 ms with one masked einsum over all S keys, the rest of the
    step the same.
    """
    b, hq, lq, d = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    block_k = CACHE_BLOCK_K if s_len % CACHE_BLOCK_K == 0 else s_len
    scale = 1.0 / math.sqrt(d)
    qg = _expand_gqa(q, hkv)  # (B, Hkv, G, C, D)

    def body(carry, i):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * block_k, block_k, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, i * block_k, block_k, axis=2)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb, preferred_element_type=jnp.float32) * scale
        kv_pos = i * block_k + jnp.arange(block_k)
        bias = jnp.where(kv_pos[None, None, :] < kv_length[:, :, None], 0.0, NEG_INF)
        s = s + bias[:, None, None]  # (B, C, block) bias, as in _block_scores
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb, preferred_element_type=jnp.float32
        )
        return (m_new, l, acc), None

    m0 = jnp.full(qg.shape[:-1], NEG_INF, jnp.float32)
    a0 = jnp.zeros(qg.shape, jnp.float32)
    (_, l, acc), _ = jax.lax.scan(
        body, (m0, jnp.zeros_like(m0), a0), jnp.arange(s_len // block_k)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, lq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP


def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    g = jnp.einsum("bsd,df->bsf", x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("bsd,df->bsf", x, w_up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, w_down, preferred_element_type=jnp.float32).astype(x.dtype)


def gelu_mlp(x: Array, w_in: Array, b_in: Array, w_out: Array, b_out: Array) -> Array:
    h = jnp.einsum("bsd,df->bsf", x, w_in, preferred_element_type=jnp.float32) + b_in
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return (
        jnp.einsum("bsf,fd->bsd", h, w_out, preferred_element_type=jnp.float32) + b_out
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# losses


def cross_entropy(logits: Array, labels: Array, vocab_size: int, z_coef: float = 1e-4):
    """Mean CE over labels >= 0; logits padding beyond vocab_size is masked.

    logits: (B, S, Vp) any float dtype; labels: (B, S) int32 with -1 = ignore.
    Returns (loss, metrics dict).
    """
    vp = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if vp > vocab_size:
        pad_mask = jnp.arange(vp) >= vocab_size
        lf = jnp.where(pad_mask[None, None, :], NEG_INF, lf)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    zloss = z_coef * ((lse * mask) ** 2).sum() / denom
    # accuracy via gold==max (an argmax would materialize a vocab-sized iota)
    metrics = {
        "loss": loss,
        "zloss": zloss,
        "tokens": mask.sum(),
        "accuracy": ((gold >= lf.max(-1)) * mask).sum() / denom,
    }
    return loss + zloss, metrics


def fused_ce_loss(
    h: Array,
    w: Array,
    labels: Array,
    vocab_size: int,
    *,
    chunk: int = 1024,
    z_coef: float = 1e-4,
):
    """Sequence-chunked fused lm_head + cross-entropy.

    Never materializes the full (B, S, Vp) logits: the head matmul and the
    CE run one seq-chunk at a time inside a checkpointed scan (backward
    recomputes each chunk's logits). For 150k-vocab configs this removes
    the single largest train-step allocation (f32 logits + softmax +
    dlogits). h: (B, S, D) post-final-norm; w: (D, Vp); labels: (B, S)
    int32 with -1 = ignore. Returns (loss, metrics) like ``cross_entropy``.
    """
    from repro.launch.mesh import BATCH, MODEL, shard  # local: avoid cycle

    b, s, d = h.shape
    vp = w.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (s + pad) // chunk
    hs = h.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)  # (nc, B, C, D)
    ls = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    vocab_bias = jnp.where(jnp.arange(vp) < vocab_size, 0.0, NEG_INF).astype(jnp.float32)

    def body(carry, xs):
        nll, zz, ntok, ncorr = carry
        hc, lc = xs
        logits = jnp.einsum(
            "bcd,dv->bcv", hc, w.astype(hc.dtype), preferred_element_type=jnp.float32
        )
        logits = shard(logits + vocab_bias, BATCH, None, MODEL)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        msk = (lc >= 0).astype(jnp.float32)
        nll = nll + ((lse - gold) * msk).sum()
        zz = zz + ((lse * msk) ** 2).sum()
        ntok = ntok + msk.sum()
        ncorr = ncorr + ((gold >= logits.max(-1)) * msk).sum()
        return (nll, zz, ntok, ncorr), None

    body = jax.checkpoint(body, prevent_cse=False)
    zero = jnp.zeros((), jnp.float32)
    (nll, zz, ntok, ncorr), _ = jax.lax.scan(body, (zero, zero, zero, zero), (hs, ls))
    denom = jnp.maximum(ntok, 1.0)
    loss = nll / denom
    zloss = z_coef * zz / denom
    metrics = {"loss": loss, "zloss": zloss, "tokens": ntok, "accuracy": ncorr / denom}
    return loss + zloss, metrics


# ---------------------------------------------------------------------------
# misc


def causal_positions(batch: int, seq: int) -> Array:
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq))


def sinusoidal_positions(length: int, d_model: int) -> Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d_model, 2, dtype=jnp.float32) * (-math.log(10000.0) / d_model))
    pe = jnp.zeros((length, d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def maybe_remat(fn, enabled: bool, policy: str = "nothing"):
    """Per-layer activation checkpointing.

    policy="nothing": save only the inter-layer residual stream (minimum
    memory, ~1/3 more compute in backward) — the default so every assigned
    cell fits per-chip HBM; policy="dots": additionally save matmul outputs
    (less recompute, more memory) — a §Perf lever for compute-bound cells.
    """
    if not enabled:
        return fn
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    return jax.checkpoint(fn, policy=policies[policy])


def cast_tree(tree, dtype):
    """Cast float leaves of a weight (sub)tree to the compute dtype.

    Matmuls must see bf16 weights: mixed f32xbf16 einsums promote the
    activations to f32, which silently turns the whole residual stream and
    every saved remat buffer f32 (2x memory) and pushes the MXU off its
    bf16 path (TPU peak is quoted in bf16).
    """
    return jax.tree.map(
        lambda w: w.astype(dtype) if jnp.issubdtype(w.dtype, jnp.floating) else w,
        tree,
    )


def constrain_tree(tree, specs, dtype=None):
    """Constrain a (sub)tree of weights to its compute (TP) layout (+cast).

    No-op when the weights are already in that layout (the non-pooled path)
    or when no mesh is active (CPU tests). With pooled / ZeRO storage this is
    the just-in-time gather of the paper's shared-L2 pooling: called on one
    scanned layer slice at a time, it keeps a single layer's gathered weights
    live instead of the whole tree, and its transpose under jax.grad is the
    per-layer reduce-scatter of the gradients back to the pooled layout.
    The cast happens BEFORE the constraint so the gather moves bf16 bytes.
    """
    from repro.launch import mesh as _meshlib

    def one(w, s):
        if dtype is not None and jnp.issubdtype(w.dtype, jnp.floating):
            w = w.astype(dtype)
        return _meshlib.shard(w, *s)

    return jax.tree.map(one, tree, specs, is_leaf=lambda x: isinstance(x, jax.Array))
