"""Serve smollm-360m at full width on a TPU through ServingEngine.

    python chip_smoke.py              # one chip: serving phase + kernel oracle phase
    python chip_smoke.py --chips 4    # four chips: sharded engine vs one-chip engine

One chip. The model is ``get_config("smollm-360m")`` at its published
widths (f32 parameters, bf16 compute), random weights from ``--seed``.
The engine is the chunked single-dispatch step (continuous batching,
128-token prefill chunks) with device tiering on, so every step runs one
model dispatch and one compiled tiered-gather kernel over the near/far
page store. Sixteen requests from the paper's ``Reader`` profile (prompt
mean 512, decode mean 64) are driven through ``ServingEngine.run``. The
run fails unless every request finishes, the decoded tokens equal the
granted decode budgets, and the near and far hits drained from the
device counter plane add up to the page ids handed to the kernel. A
second, short engine with identity scales and the verify probe must
then read every tiered row bit-equal to the flat mirror
(``tiered_max_err == 0``): the compiled kernel selects, dequantizes and
counts as the interpret-mode oracle does.

Four chips (``--chips 4``). ``ShardedServingEngine`` with
``model_shards=4`` over ``make_serving_mesh(4)`` serves the same 16
requests in lockstep with the one-chip ``ServingEngine``. Each device's
bytes in use are printed; the same requests must finish, with equal
decoded tokens and equal merged near and far hits. The share of
identical output tokens is printed (bf16 may flip a few argmaxes).

The last line of standard output is one JSON object naming the device;
it is printed only when every check passed. With no TPU the script
exits non-zero and names the backend it found. The throughput lines are
host-clock smoke readings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

try:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.workloads import get_profile
    from repro.data.requests import RequestGenerator
    from repro.kernels._interpret import ENV_VAR as INTERPRET_ENV
    from repro.kernels._interpret import resolve_interpret
    from repro.models.api import get_model
    from repro.runtime.serving import EngineConfig, ServingEngine
except ImportError as e:  # run outside a checkout of the repository
    raise SystemExit(f"chip_smoke: cannot import the repository's code ({e})")

ARCH = "smollm-360m"
ENGINE = EngineConfig(
    max_batch=8,
    max_len=2048,
    page_size=16,
    n_pages=2048,
    near_frac=0.30,
    device_tiering=True,
    prefill_chunk=128,
)
N_REQUESTS = 16
PROMPT_MEAN = 512
DECODE_MEAN = 64
VERIFY_REQUESTS = 4
# the verify engine's near tier holds 40 of its 2048 pages, so its four
# requests read both tiers (at 30% every page they touch would be near)
VERIFY_NEAR_FRAC = 0.02


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def say(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def traffic(cfg, seed: int, prompt_mean: int, decode_mean: int) -> RequestGenerator:
    prof = dataclasses.replace(
        get_profile("Reader"), prompt_mean=prompt_mean, decode_mean=decode_mean
    )
    return RequestGenerator(prof, vocab_size=cfg.vocab_size, seed=seed)


def granted_budget(gen: RequestGenerator, n: int, max_len: int) -> int:
    """Decode tokens the engine owes ``n`` requests from ``gen``: each
    prompt is cut to ``max_len - 2`` tokens and its decode length to what
    is left of ``max_len``."""
    total = 0
    for _ in range(n):
        req = next(gen)
        prompt = min(len(req.tokens), max(1, max_len - 2))
        total += max(1, min(req.decode_len, max_len - prompt - 1))
    return total


def count_kernel_ids(eng: ServingEngine) -> list:
    """Count the page ids the engine hands to the segmented kernel
    (before the store pads them to a bucket)."""
    handed = [0]
    lookup = eng.tiered.lookup_segments

    def counted(page_ids, *args, **kw):
        handed[0] += len(page_ids)
        return lookup(page_ids, *args, **kw)

    eng.tiered.lookup_segments = counted
    return handed


def bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def block(eng: ServingEngine):
    jax.block_until_ready((eng.cache, eng.next_tokens))


def serve_phase(cfg, ecfg: EngineConfig, *, seed: int, n_requests: int,
                prompt_mean: int, decode_mean: int, api=None, params=None) -> dict:
    """The main path: ``n_requests`` Reader requests through
    ``ServingEngine.run``, with its counting checks. Returns the readings."""
    if api is None:
        api = get_model(cfg)
        params = api.init(jax.random.PRNGKey(seed))
    eng = ServingEngine(api, params, ecfg, seed=seed)
    handed = count_kernel_ids(eng)
    gen = traffic(cfg, seed, prompt_mean, decode_mean)
    t0 = time.perf_counter()
    eng.run(gen, n_requests=n_requests, max_steps=1)  # admits all, compiles
    block(eng)
    first = time.perf_counter() - t0
    t1 = time.perf_counter()
    stats = eng.run(gen, n_requests=0)
    block(eng)
    rest = time.perf_counter() - t1
    dev = stats["device_tiering"]
    budget = granted_budget(
        traffic(cfg, seed, prompt_mean, decode_mean), n_requests, ecfg.max_len
    )
    out = {
        "api": api,
        "params": params,
        "first_step_s": first,
        "rest_s": rest,
        "steps": eng.engine_steps,
        "tokens_decoded": stats["tokens_decoded"],
        "budget": budget,
        "finished": sorted(eng.finished),
        "near_hits": dev["near_hits"],
        "far_hits": dev["far_hits"],
        "kernel_ids": handed[0],
        "dispatches_per_step": dev["dispatches_per_step"],
        "model_dispatches_per_step": stats["serving"]["model_dispatches_per_step"],
    }
    check(out["finished"] == list(range(n_requests)),
          f"finished {out['finished']} of {n_requests} requests")
    check(out["tokens_decoded"] == budget,
          f"tokens_decoded {out['tokens_decoded']} != granted budgets {budget}")
    check(dev["near_hits"] + dev["far_hits"] == handed[0],
          f"near {dev['near_hits']} + far {dev['far_hits']} != "
          f"{handed[0]} page ids handed to the kernel")
    return out


def verify_phase(cfg, ecfg: EngineConfig, *, seed: int, n_requests: int,
                 prompt_mean: int, decode_mean: int, api, params) -> dict:
    """Tiered reads against the flat mirror, bit for bit, under identity
    scales (lossless far tier): the kernel's select, dequant and counts."""
    vcfg = dataclasses.replace(
        ecfg, near_frac=VERIFY_NEAR_FRAC, tiered_identity_scales=True, tiered_verify=True
    )
    eng = ServingEngine(api, params, vcfg, seed=seed)
    handed = count_kernel_ids(eng)
    stats = eng.run(traffic(cfg, seed + 1, prompt_mean, decode_mean), n_requests)
    dev = stats["device_tiering"]
    out = {
        "tiered_max_err": eng.tiered_max_err,
        "steps": eng.engine_steps,
        "near_hits": dev["near_hits"],
        "far_hits": dev["far_hits"],
        "kernel_ids": handed[0],
    }
    check(len(eng.finished) == n_requests,
          f"verify engine finished {len(eng.finished)} of {n_requests}")
    check(dev["near_hits"] > 0 and dev["far_hits"] > 0,
          f"verify run must read both tiers (near {dev['near_hits']}, far {dev['far_hits']})")
    check(dev["near_hits"] + dev["far_hits"] == handed[0],
          "verify engine hits do not add up to the page ids handed to the kernel")
    check(eng.tiered_max_err == 0.0,
          f"tiered reads differ from the flat mirror by {eng.tiered_max_err}")
    return out


def sharded_phase(cfg, ecfg: EngineConfig, *, seed: int, n_requests: int,
                  prompt_mean: int, decode_mean: int, shards: int) -> dict:
    """The sharded engine against the one-chip engine, stepped in lockstep
    on the same requests. Scheduling depends on lengths only, so both
    engines hold the same requests in the same slots at every step."""
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime.sharded import ShardedServingEngine

    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(seed))
    devices = jax.devices()[:shards]
    one = ServingEngine(api, params, ecfg, seed=seed)
    before = bytes_in_use(devices)
    many = ShardedServingEngine(
        api, params, dataclasses.replace(ecfg, model_shards=shards), seed=seed,
        mesh=make_serving_mesh(shards),
    )
    after = bytes_in_use(devices)
    engines = (one, many)
    for eng in engines:
        gen = traffic(cfg, seed, prompt_mean, decode_mean)
        for _ in range(n_requests):
            eng.submit(next(gen))
    same = total = steps = 0
    t0 = time.perf_counter()
    while one.queue or any(s.active for s in one.slots):
        for eng in engines:
            eng.step()
        steps += 1
        check([s.seq_id for s in one.slots] == [s.seq_id for s in many.slots],
              f"slot assignment diverged at step {steps}")
        toks = [np.asarray(eng.next_tokens) for eng in engines]
        for i, s in enumerate(one.slots):
            if s.active and not s.prefilling:  # emitted a token this step
                total += 1
                same += int(toks[0][i] == toks[1][i])
    wall = time.perf_counter() - t0
    s1, s4 = one.stats(), many.stats()
    d1, d4 = s1["device_tiering"], s4["device_tiering"]
    out = {
        "steps": steps,
        "wall_s": wall,
        "sharded_bytes_per_device": [a - b for a, b in zip(after, before)],
        "bytes_in_use": bytes_in_use(devices),
        "finished": (sorted(one.finished), sorted(many.finished)),
        "tokens_decoded": (s1["tokens_decoded"], s4["tokens_decoded"]),
        "near_hits": (d1["near_hits"], d4["near_hits"]),
        "far_hits": (d1["far_hits"], d4["far_hits"]),
        "shard_dispatches": d4["shard_dispatches"],
        "same_tokens": same,
        "compared_tokens": total,
    }
    check(out["finished"][0] == out["finished"][1] == list(range(n_requests)),
          f"finished requests differ: {out['finished']}")
    check(out["tokens_decoded"][0] == out["tokens_decoded"][1],
          f"tokens_decoded differ: {out['tokens_decoded']}")
    check(out["near_hits"][0] == out["near_hits"][1]
          and out["far_hits"][0] == out["far_hits"][1],
          f"near/far totals differ: near {out['near_hits']} far {out['far_hits']}")
    check(all(b > 0 for b in out["sharded_bytes_per_device"]),
          f"a device holds none of the sharded engine: {out['sharded_bytes_per_device']}")
    return out


def require_tpu(chips: int):
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; this smoke runs only on a TPU"
        )
    if resolve_interpret(None):
        raise SystemExit(
            f"chip_smoke: {INTERPRET_ENV} forces interpret-mode kernels; unset it"
        )
    n = len(jax.devices())
    if n < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} devices, found {n}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    require_tpu(args.chips)

    from repro.launch.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)  # published widths: no .reduced()
    dev0 = jax.devices()[0]
    say(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, {cfg.n_heads}/"
        f"{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}, params {cfg.param_dtype}, "
        f"compute {cfg.compute_dtype}; device {dev0.device_kind} x{len(jax.devices())}")
    lengths = dict(n_requests=N_REQUESTS, prompt_mean=PROMPT_MEAN, decode_mean=DECODE_MEAN)

    if args.chips == 4:
        r = sharded_phase(cfg, ENGINE, seed=args.seed, shards=4, **lengths)
        say(f"sharded x4 vs one chip: {r['steps']} lockstep steps in {r['wall_s']:.1f} s "
            f"(host clock, both engines)")
        say(f"bytes the sharded engine added per device: {r['sharded_bytes_per_device']}")
        say(f"bytes_in_use per device after the run: {r['bytes_in_use']}")
        say(f"finished {len(r['finished'][1])}/{N_REQUESTS} on both; tokens_decoded "
            f"{r['tokens_decoded']}; near {r['near_hits']} far {r['far_hits']}; "
            f"kernel dispatches per shard {r['shard_dispatches']}")
        say(f"identical output tokens: {r['same_tokens']}/{r['compared_tokens']} "
            f"({r['same_tokens'] / max(r['compared_tokens'], 1):.4f})")
    else:
        r = serve_phase(cfg, ENGINE, seed=args.seed, **lengths)
        tps = r["tokens_decoded"] / r["rest_s"]
        say(f"first step (admit 16, chunk-step compile): {r['first_step_s']:.2f} s")
        say(f"served {len(r['finished'])}/{N_REQUESTS} requests, {r['tokens_decoded']} "
            f"tokens (= granted budgets) in {r['steps']} steps")
        say(f"smoke reading, host clock after block_until_ready (not a metric): "
            f"{(r['steps'] - 1) / r['rest_s']:.3f} steps/s, {tps:.2f} tokens/s "
            f"over {r['rest_s']:.2f} s after the first step")
        say(f"tiered-gather hits: near {r['near_hits']} far {r['far_hits']} "
            f"= {r['kernel_ids']} page ids; kernel dispatches/step "
            f"{r['dispatches_per_step']:.3f}, model dispatches/step "
            f"{r['model_dispatches_per_step']:.3f}")
        v = verify_phase(cfg, ENGINE, seed=args.seed, n_requests=VERIFY_REQUESTS,
                         prompt_mean=PROMPT_MEAN, decode_mean=16,
                         api=r["api"], params=r["params"])
        say(f"verify engine: {v['steps']} steps, near {v['near_hits']} far "
            f"{v['far_hits']}, tiered_max_err {v['tiered_max_err']}")
    stats = dev0.memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
