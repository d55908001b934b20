"""Unified model API: one object per architecture family exposing

  init / param_specs / loss / prefill / decode / init_cache / cache_specs /
  input_specs (ShapeDtypeStruct stand-ins per assigned shape) / batch_specs

plus step builders (train / prefill / serve) shared by the trainer, the
serving engine, and launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, ShapeSpec
from repro.core import pooling
from repro.launch.mesh import BATCH, MODEL
from repro.models import common, moe, rwkv6, transformer, vlm, whisper, zamba2
from repro.optim import AdamWConfig, adamw_update

Array = jax.Array
_I32 = jnp.int32
_BF16 = jnp.bfloat16


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig

    # ------------------------------------------------------------------
    @property
    def family(self) -> str:
        return self.cfg.family

    def init(self, key) -> dict:
        return _MODULES[self.family].init(key, self.cfg)

    def abstract_params(self) -> dict:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def param_specs(self) -> dict:
        mod = _MODULES[self.family]
        return mod.param_specs(self.cfg)

    def cache_specs(self) -> dict:
        return _MODULES[self.family].cache_specs(self.cfg)

    def init_cache(self, batch: int, max_len: int) -> dict:
        return _MODULES[self.family].init_cache(self.cfg, batch, max_len)

    def abstract_cache(self, batch: int, max_len: int) -> dict:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    # ------------------------------------------------------------------
    def loss(self, params: dict, batch: dict, *, remat: Optional[bool] = None):
        """Trunk + fused seq-chunked lm_head/CE (+ MoE aux).

        Full (B, S, Vp) logits are never materialized — the head matmul and
        the CE run chunk-by-chunk (common.fused_ce_loss), which is what lets
        the 150k-vocab train cells fit per-chip HBM. Returns (loss, metrics).
        """
        cfg = self.cfg
        ce = functools.partial(common.fused_ce_loss, labels=batch["labels"], vocab_size=cfg.vocab_size)
        if self.family == "dense":
            h, w = transformer.features(params, cfg, batch["tokens"], remat=remat)
            return ce(h, w)
        if self.family == "moe":
            h, w, aux = moe.features(params, cfg, batch["tokens"], remat=remat)
            loss, metrics = ce(h, w)
            metrics["aux_loss"] = aux
            return loss + aux, metrics
        if self.family == "ssm":
            h, w = rwkv6.features(params, cfg, batch["tokens"], remat=remat)
            return ce(h, w)
        if self.family == "hybrid":
            h, w = zamba2.features(params, cfg, batch["tokens"], remat=remat)
            return ce(h, w)
        if self.family == "vlm":
            h, w = vlm.features(
                params, cfg, batch["embeds"], batch["mrope_positions"], remat=remat
            )
            return ce(h, w)
        if self.family == "audio":
            h, w = whisper.features(params, cfg, batch["tokens"], batch["frames"], remat=remat)
            return ce(h, w)
        raise ValueError(self.family)

    def prefill(self, params: dict, batch: dict, *, max_len: int):
        cfg = self.cfg
        if self.family == "dense":
            return transformer.prefill(params, cfg, batch["tokens"], max_len=max_len)
        if self.family == "moe":
            return moe.prefill(params, cfg, batch["tokens"], max_len=max_len)
        if self.family == "ssm":
            return rwkv6.prefill(params, cfg, batch["tokens"], max_len=max_len)
        if self.family == "hybrid":
            return zamba2.prefill(params, cfg, batch["tokens"], max_len=max_len)
        if self.family == "vlm":
            return vlm.prefill(
                params, cfg, batch["embeds"], batch["mrope_positions"], max_len=max_len
            )
        if self.family == "audio":
            return whisper.prefill(params, cfg, batch["tokens"], batch["frames"], max_len=max_len)
        raise ValueError(self.family)

    def decode(self, params: dict, cache: dict, tokens: Array):
        return _MODULES[self.family].decode_step(params, self.cfg, cache, tokens)

    @property
    def block_decode(self) -> bool:
        """Whether the family's module feeds a (B, C) token block through
        one pass (``decode_block``) rather than C single-token decodes."""
        return hasattr(_MODULES[self.family], "decode_block")

    def decode_block(self, params: dict, cache: dict, tokens: Array, n: Array, out_cols: Array):
        return _MODULES[self.family].decode_block(params, self.cfg, cache, tokens, n, out_cols)

    # ------------------------------------------------------------------
    # assigned-shape input stand-ins (global shapes; no allocation)

    def input_specs(self, shape_name: str) -> dict:
        """ShapeDtypeStruct tree for the step function of this shape cell."""
        cfg, sh = self.cfg, SHAPES[shape_name]
        b, s = sh.global_batch, sh.seq_len
        tok = lambda shape: jax.ShapeDtypeStruct(shape, _I32)
        emb = lambda shape: jax.ShapeDtypeStruct(shape, _BF16)
        if sh.kind in ("train", "prefill"):
            if self.family == "vlm":
                batch = {"embeds": emb((b, s, cfg.d_model)), "mrope_positions": tok((3, b, s))}
            elif self.family == "audio":
                batch = {"tokens": tok((b, s)), "frames": emb((b, cfg.n_audio_frames, cfg.d_model))}
            else:
                batch = {"tokens": tok((b, s))}
            if sh.kind == "train":
                batch["labels"] = tok((b, s))
            return batch
        # decode: one new token against a cache filled to s
        return {"tokens": tok((b, 1)), "cache": self.abstract_cache(b, s)}

    def batch_specs(self, shape_name: str) -> dict:
        """PartitionSpec tuples matching input_specs(shape_name)."""
        sh = SHAPES[shape_name]
        specs: dict[str, Any] = {}
        if sh.kind in ("train", "prefill"):
            if self.family == "vlm":
                specs["embeds"] = (BATCH, None, None)
                specs["mrope_positions"] = (None, BATCH, None)
            elif self.family == "audio":
                specs["tokens"] = (BATCH, None)
                specs["frames"] = (BATCH, None, None)
            else:
                specs["tokens"] = (BATCH, None)
            if sh.kind == "train":
                specs["labels"] = (BATCH, None)
            return specs
        return {"tokens": (BATCH, None), "cache": self.cache_specs()}


def get_model(cfg: ModelConfig) -> ModelAPI:
    return ModelAPI(cfg)


_MODULES = {
    "dense": transformer,
    "moe": moe,
    "ssm": rwkv6,
    "hybrid": zamba2,
    "vlm": vlm,
    "audio": whisper,
}


# ---------------------------------------------------------------------------
# step builders


def make_train_step(
    api: ModelAPI,
    opt_cfg: AdamWConfig,
    *,
    compute_specs: Optional[dict] = None,
    donate: bool = True,
    grad_accum: Optional[int] = None,
    storage_specs: Optional[dict] = None,
):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``compute_specs``: when weight pooling is on, params arrive POOL-sharded;
    the step gathers them to the compute (TP) layout inside loss_fn — the
    backward transpose reduce-scatters grads back to the pooled layout.

    ``grad_accum`` (default cfg.grad_accum): microbatched gradient
    accumulation via lax.scan. Remat/activation stacks scale as 1/A while
    collectives and the optimizer run once per step — the standard lever
    that fits long-stack (many-layer x 4k-seq) train cells into per-chip
    HBM without resharding the model.

    ``storage_specs``: PartitionSpec tuples for the parameter tree. The
    grad-accumulation buffer is constrained to this layout — without it
    GSPMD materializes REPLICATED f32 accumulators (full per-layer weight
    stacks on every chip).
    """
    ga = grad_accum if grad_accum is not None else api.cfg.grad_accum

    def loss_fn(p, batch):
        if compute_specs is not None:
            p = pooling.gather(p, compute_specs)
        return api.loss(p, batch)

    def train_step(params, opt_state, batch):
        if ga > 1:
            from repro.launch import mesh as meshlib
            from repro.launch.mesh import BATCH

            def split(x):
                b = x.shape[0]
                assert b % ga == 0, (b, ga)
                x = x.reshape(ga, b // ga, *x.shape[1:])
                return meshlib.shard(x, None, BATCH)

            # vlm mrope positions carry batch on dim 1: split on the right axis
            def split_leaf(k, x):
                if k == "mrope_positions":
                    t, b = x.shape[0], x.shape[1]
                    x = x.reshape(t, ga, b // ga, *x.shape[2:]).transpose(1, 0, 2, *range(3, x.ndim + 1))
                    return meshlib.shard(x, None, None, BATCH)
                return split(x)

            micro_batches = {k: split_leaf(k, v) for k, v in batch.items()}
            if storage_specs is not None:
                gzero = jax.tree.map(
                    lambda p, s: meshlib.shard(jnp.zeros(p.shape, jnp.float32), *s),
                    params,
                    storage_specs,
                    is_leaf=lambda x: isinstance(x, jax.Array),
                )
            else:
                gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def micro(carry, mb):
                gsum, msum = carry
                (_, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                g = _constrain_grads(g, storage_specs)
                gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
                msum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), msum, metrics)
                return (gsum, msum), None

            m0 = jax.eval_shape(lambda: loss_fn(params, jax.tree.map(lambda x: x[0], micro_batches))[1])
            mzero = jax.tree.map(lambda s: jnp.zeros((), jnp.float32), m0)
            (grads, msum), _ = jax.lax.scan(micro, (gzero, mzero), micro_batches)
            grads = jax.tree.map(lambda g: g / ga, grads)
            metrics = jax.tree.map(lambda m: m / ga, msum)
        else:
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads = _constrain_grads(grads, storage_specs)
        params_new, opt_state, om = adamw_update(opt_cfg, params, grads, opt_state)
        return params_new, opt_state, {**metrics, **om}

    return train_step


def _constrain_grads(grads, storage_specs):
    """Pin gradients to the parameter storage layout.

    Without this GSPMD can leave scan-transposed per-layer grads replicated
    (a full all-reduce instead of a reduce-scatter), which then replicates
    the whole grad-accum + AdamW elementwise pipeline — full (L, D, D) f32
    stacks on every chip.
    """
    if storage_specs is None:
        return grads
    from repro.launch import mesh as meshlib

    return jax.tree.map(
        lambda g, s: meshlib.shard(g, *s),
        grads,
        storage_specs,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )


def make_prefill_step(api: ModelAPI, max_len: int):
    """(params, batch) -> (next_token_logits (B, Vp), cache)."""

    def prefill_step(params, batch):
        logits, cache = api.prefill(params, batch, max_len=max_len)
        return logits[:, -1, :], cache

    return prefill_step


def make_serve_step(api: ModelAPI, *, sample: str = "greedy",
                    vocab: Optional[int] = None):
    """(params, cache, tokens (B,1)) -> (next_tokens (B,1), cache').

    ``vocab`` restricts the argmax to the first ``vocab`` logits — models
    pad their output head to a lane multiple, and a serving caller must
    never sample a padding id. The serving engine's jitted decode is this
    step (with the vocab slice and donated cache), so decode + fused
    argmax has exactly one implementation.
    """

    def serve_step(params, cache, tokens):
        logits, cache = api.decode(params, cache, tokens)
        v = logits.shape[-1] if vocab is None else vocab
        nxt = jnp.argmax(logits[:, -1, :v], axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return serve_step
