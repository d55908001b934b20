"""Plain reference of a dense decoder (Llama-style: RMSNorm, RoPE with
rotate-half, grouped-query causal attention, SwiGLU), in float32 at the
highest matmul precision.

It imports nothing of the program. It reads the configuration's sizes,
makes its weights from the seed (``make``, below), and runs one whole
sequence at a time, so that its peak stays small beside the chip.

``quant`` selects the control: the same forward with every matmul operand
of the linear layers rounded to a lower precision (``int8``: symmetric
absmax per output channel for weights and per token for activations;
``fp8``: e4m3 mantissa with a per-tensor scale). The benchmark's runs use
``f32`` only; the control is read by ``bench/control.py``.

Weights. The benchmark makes the weights with ``make`` and hands them to
the program, so that the reference can make the same ones again without taking anything from the program. The tree has the
layout the program's dense family serves (stacked layers, flat projections,
output head padded to a multiple of 256 columns); ``bench/harness.py``
checks it against the program's own shapes before serving. Values follow a
standard initialisation that keeps activations of order one over depth:
fan-in normals, output projections scaled by ``1/sqrt(2 * layers)``,
embeddings ``N(0, 0.02)``, norm weights one.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _int8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    m, e = jnp.frexp(x / s)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) * s  # 3 mantissa bits


def _mm(x, w, quant):
    """(S, din) @ (din, dout), operands rounded as ``quant`` says."""
    if quant == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, hd), position = row index."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def forward(params, tokens, *, hq, hkv, vocab, eps, theta, quant="f32"):
    """Logits (S, vocab) at every position, and each layer's K (after
    RoPE) and V: (L, S, hkv, hd)."""
    s = tokens.shape[0]
    x = params["embed"][tokens]
    d = x.shape[-1]
    hd = d // hq
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _rms(x, p["ln1"], eps)
        q = _rope(_mm(h, a["wq"], quant).reshape(s, hq, hd), theta)
        k = _rope(_mm(h, a["wk"], quant).reshape(s, hkv, hd), theta)
        v = _mm(h, a["wv"], quant).reshape(s, hkv, hd)
        kk = jnp.repeat(k, hq // hkv, axis=1)
        vv = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, kk, precision=HIGHEST) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), vv, precision=HIGHEST)
        x = x + _mm(o.reshape(s, hq * hd), a["wo"], quant)
        h = _rms(x, p["ln2"], eps)
        g = _mm(h, m["w_gate"], quant)
        u = _mm(h, m["w_up"], quant)
        x = x + _mm(jax.nn.silu(g) * u, m["w_down"], quant)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    h = _rms(x, params["final_norm"], eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return _mm(h, head, quant)[:, :vocab], ks, vs


def _rows(ks, vs, pos):
    """KV payload rows at positions ``pos``: every layer's K, then every
    layer's V, each (hkv, hd), flattened."""
    k = ks[:, pos]  # (L, R, hkv, hd)
    v = vs[:, pos]
    kv = jnp.concatenate([k, v], axis=0)  # (2L, R, hkv, hd)
    return kv.transpose(1, 0, 2, 3).reshape(pos.shape[0], -1)


def _gap(logits, targets):
    """How far each target's logit lies below the best logit of its row."""
    return jnp.max(logits, -1) - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "vocab", "eps", "theta"))
def check(params, tokens, targets, row_pos, *, hq, hkv, vocab, eps, theta):
    """Reference readings for one sequence: the gap of each target token
    (the token served after that position), and the KV rows at ``row_pos``."""
    logits, ks, vs = forward(params, tokens, hq=hq, hkv=hkv, vocab=vocab, eps=eps, theta=theta)
    return _gap(logits, targets), _rows(ks, vs, row_pos)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "vocab", "eps", "theta", "quant"))
def control(params, tokens, row_pos, *, hq, hkv, vocab, eps, theta, quant):
    """The control's readings: at each position, the gap (against the
    float32 reference) of the token the lower precision puts first, and
    the lower precision's KV rows at ``row_pos``."""
    kw = dict(hq=hq, hkv=hkv, vocab=vocab, eps=eps, theta=theta)
    ref, _, _ = forward(params, tokens, **kw)
    low, ks, vs = forward(params, tokens, quant=quant, **kw)
    return _gap(ref, jnp.argmax(low, -1)), _rows(ks, vs, row_pos)


def static_args(model: dict) -> dict:
    return dict(
        hq=model["num_attention_heads"],
        hkv=model["num_key_value_heads"],
        vocab=model["vocab_size"],
        eps=float(model["rms_norm_eps"]),
        theta=float(model["rope_theta"]),
    )


# ---------------------------------------------------------------------------
# weights



VOCAB_MULTIPLE = 256


def key(seed: int):
    """A PRNG key from any non-negative seed (two 32-bit words, none lost)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    hq = model["num_attention_heads"]
    return {
        "d": d,
        "L": model["num_hidden_layers"],
        "hq": hq,
        "hkv": model["num_key_value_heads"],
        "hd": d // hq,
        "f": model["intermediate_size"],
        "vocab": model["vocab_size"],
        "vp": -(-model["vocab_size"] // VOCAB_MULTIPLE) * VOCAB_MULTIPLE,
        "tied": bool(model["tie_word_embeddings"]),
    }


def shapes(model: dict) -> dict:
    z = dims(model)
    d, L, f = z["d"], z["L"], z["f"]
    q, kv = z["hq"] * z["hd"], z["hkv"] * z["hd"]
    tree = {
        "embed": (z["vp"], d),
        "layers": {
            "ln1": (L, d),
            "ln2": (L, d),
            "attn": {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv), "wo": (L, q, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)},
        },
        "final_norm": (d,),
    }
    if not z["tied"]:
        tree["lm_head"] = (d, z["vp"])
    return tree


def _leaf(name: str, shape, k, depth: int):
    if name in ("ln1", "ln2", "final_norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "embed":
        return jax.random.normal(k, shape, jnp.float32) * 0.02
    std = shape[-2] ** -0.5  # fan-in
    if name in ("wo", "w_down"):
        std /= (2 * depth) ** 0.5
    return jax.random.normal(k, shape, jnp.float32) * std


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec: tuple, k):
    flat, depth = spec
    keys = jax.random.split(k, len(flat))
    return [_leaf(name, shape, kk, depth) for (name, shape), kk in zip(flat, keys)]


def make(model: dict, seed: int) -> dict:
    """The parameter tree, float32 on the default device."""
    tree = shapes(model)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    flat = tuple((p[-1].key, s) for p, s in paths)
    leaves = _make((flat, model["num_hidden_layers"]), key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
