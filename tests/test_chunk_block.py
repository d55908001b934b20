"""The dense chunk step's block pass against the column scan it replaced.

``make_chunk_step`` runs a dense model's continuous-batching step as one
(B, C) pass (``transformer.decode_block``). The oracle here is a local copy
of the column scan — C single-token decode steps, each gating the whole
cache per row — kept in this file so that the program's remaining scan
(for the families without a block pass) can change without moving it.

On every case the block step must give the scan's ``nxt``, the same K/V
rows within bf16 tolerance at the positions the step writes, the same
``lengths``, and a cache that is bit-identical everywhere else: idle rows,
positions past each row's fed columns, and positions near ``max_len``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.workloads import get_profile
from repro.data.requests import RequestGenerator
from repro.models import common
from repro.models.api import get_model, make_serve_step
from repro.runtime.serving import EngineConfig, ServingEngine, make_chunk_step
from repro.runtime.sharded import ShardedServingEngine

B, C, S = 4, 8, 32
S_LONG = 2 * common.CACHE_BLOCK_K  # two online-softmax key blocks
_API = get_model(get_config("smollm-360m").reduced())
_BF16_TOL = 2.0 ** -7  # two bf16 ulps at magnitude 1


def _column_scan(params, cache, nxt, tok, use_prompt, active, emit):
    serve = make_serve_step(_API, vocab=_API.cfg.vocab_size)

    def col(carry, xs):
        cache, nxt = carry
        tok_c, up_c, act_c, em_c = xs
        t = jnp.where(up_c, tok_c, nxt)
        out, new_cache = serve(params, cache, t[:, None])

        def gate(new, old):
            if new.ndim == 1:
                return jnp.where(act_c, new, old)
            m = act_c.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(m, new, old)

        cache = jax.tree.map(gate, new_cache, cache)
        return (cache, jnp.where(em_c, out[:, 0], nxt)), None

    (cache, nxt), _ = jax.lax.scan(
        col, (cache, nxt), (tok.T, use_prompt.T, active.T, emit.T)
    )
    return nxt, cache


_ORACLE = jax.jit(_column_scan)
_BLOCK = jax.jit(make_chunk_step(_API))


@pytest.fixture(scope="module")
def params():
    return _API.init(jax.random.PRNGKey(0))


def _rows(rng, lengths, plan):
    """Masks for ``plan``: per row ``None`` (idle), ``"decode"``, or
    ``(n, completes)`` — n prompt tokens, emitting iff the prompt completes."""
    tok = rng.integers(0, _API.cfg.vocab_size, size=(B, C)).astype(np.int32)
    use_prompt = np.zeros((B, C), bool)
    active = np.zeros((B, C), bool)
    emit = np.zeros((B, C), bool)
    for i, row in enumerate(plan):
        if row == "decode":
            active[i, 0] = emit[i, 0] = True
        elif row is not None:
            n, completes = row
            use_prompt[i, :n] = active[i, :n] = True
            emit[i, n - 1] = completes
    return tok, use_prompt, active, emit


# max_len, lengths per row, and the row plan
CASES = {
    # idle, decode, a prompt that completes mid-block, one that does not
    "mixed": (S, [5, 10, 3, 0], [None, "decode", (5, True), (C, False)]),
    # lengths + C > max_len: the writes stop at the fed columns, in range
    "near_max_len": (S, [27, S - 1, 12, 20], [(4, True), "decode", None, (2, False)]),
    "all_decode": (S, [1, 7, 19, 30], ["decode"] * B),
    # bench/harness.warm's call: every mask false
    "warm_up": (S, [0, 0, 0, 0], [None] * B),
    # keys in both key blocks, and a block boundary inside one row's columns
    "two_key_blocks": (
        S_LONG,
        [S_LONG // 2 - 3, 700, S_LONG - 2, 40],
        [(C, True), "decode", "decode", (6, False)],
    ),
}


@pytest.mark.parametrize("case", list(CASES))
def test_block_step_matches_column_scan(params, case):
    s_len, lengths, plan = CASES[case]
    rng = np.random.default_rng(len(case))
    cache = _API.init_cache(B, s_len)
    # the whole cache holds data, stale positions too, so a write or a read
    # that strays past a row's valid keys shows
    k0 = jnp.asarray(rng.standard_normal(cache["k"].shape), jnp.bfloat16)
    v0 = jnp.asarray(rng.standard_normal(cache["v"].shape), jnp.bfloat16)
    cache = {"k": k0, "v": v0, "lengths": jnp.asarray(lengths, jnp.int32)}
    nxt = jnp.asarray(rng.integers(0, _API.cfg.vocab_size, size=B), jnp.int32)
    masks = [jnp.asarray(m) for m in _rows(rng, lengths, plan)]

    want_nxt, want = _ORACLE(params, cache, nxt, *masks)
    got_nxt, got = _BLOCK(params, cache, nxt, *masks)

    np.testing.assert_array_equal(np.asarray(got_nxt), np.asarray(want_nxt))
    n = np.asarray(masks[2]).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(got["lengths"]), np.asarray(lengths) + n)
    np.testing.assert_array_equal(np.asarray(got["lengths"]), np.asarray(want["lengths"]))
    written = np.zeros((B, s_len), bool)
    for i in range(B):
        written[i, lengths[i]: lengths[i] + n[i]] = True
    for leaf, before in (("k", k0), ("v", v0)):
        g = np.asarray(got[leaf].astype(jnp.float32))  # (L, B, Hkv, S, hd)
        w = np.asarray(want[leaf].astype(jnp.float32))
        b0 = np.asarray(before.astype(jnp.float32))
        at = written[None, :, None, :, None]
        # untouched positions: bit for bit the cache that went in
        np.testing.assert_array_equal(np.where(at, 0, g), np.where(at, 0, b0))
        np.testing.assert_array_equal(np.where(at, 0, w), np.where(at, 0, b0))
        np.testing.assert_allclose(
            np.where(at, g, 0), np.where(at, w, 0), rtol=_BF16_TOL, atol=_BF16_TOL
        )
    if case == "warm_up":
        np.testing.assert_array_equal(np.asarray(got_nxt), np.asarray(nxt))


def test_block_pass_is_dense_only():
    """Only the dense family decodes a token block in one pass; the other
    chunkable families keep the column scan (tests/test_continuous_batching
    pins their tokens)."""
    for arch, block in (
        ("smollm-360m", True), ("qwen2-moe-a2.7b", False),
        ("rwkv6-7b", False), ("zamba2-1.2b", False),
    ):
        assert get_model(get_config(arch).reduced()).block_decode is block, arch


def _tokens(eng, n_requests=6):
    prof = dataclasses.replace(get_profile("Web1"), prompt_mean=24, decode_mean=8)
    gen = RequestGenerator(prof, vocab_size=_API.cfg.vocab_size, seed=5)
    for _ in range(n_requests):
        eng.submit(next(gen))
    out, steps = [], 0
    while (eng.queue or any(s.active for s in eng.slots)) and steps < 200:
        eng.step()
        out.append(np.asarray(eng.next_tokens))
        steps += 1
    assert not eng.queue and not any(s.active for s in eng.slots)
    return np.array(out)


def test_one_shard_engine_runs_the_block_step(params):
    """The sharded engine inherits the block chunk step: on a one-shard
    mesh, under its cache layout, it emits the unsharded engine's tokens."""
    kw = dict(max_batch=4, max_len=64, n_pages=256, device_tiering=True,
              tiered_identity_scales=True, prefill_chunk=C)
    base = _tokens(ServingEngine(_API, params, EngineConfig(**kw), seed=0))
    # an api of its own, so the step is traced under the engine's mesh
    api = get_model(_API.cfg)
    shrd = ShardedServingEngine(api, params, EngineConfig(model_shards=1, **kw), seed=0)
    np.testing.assert_array_equal(_tokens(shrd), base)
