"""One run of one cell: set-up, a timed window over the served path, and the
check of what the window served against the plain reference.

The timed window drives ``ServingEngine.step`` from the loop below. At each
step boundary the loop submits the requests that have arrived, calls
``step()``, and reads ``next_tokens`` to the host: the delivery a streaming
front end must make. Every token a slot emitted in that step is stamped with
the time it reached the host. Time to first token and the gaps between
tokens are taken at that delivery, never at enqueue. The readback waits for
the device, so the host and the device take turns; a delivery path inside the
engine would let them overlap. The window closes at its deadline: the step
in flight then runs to its end, and what it delivers after the close does
not count, while the time up to the close does.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import gc
import logging
import sys
import time
import types

import numpy as np

from bench import spec, stats
from bench.costs import dense_lm, tiered_gather

SRC = spec.ROOT / "src"
CACHE_DIR = spec.ROOT / ".jax_cache"  # fixed: the path is part of a cached program's key
TRACE_LIMIT_S = 10.0  # the profiler covers at most this much of a window
ROW_REQUESTS = 4  # requests whose tier-plane rows are checked
ROWS_PER_REQUEST = 8
FAR_ROWS_PER_REQUEST = 4  # of those, rows from the far tier where a request has them
MAX_REF_REQUESTS = 16
MIN_REF_TOKENS = 256


def program():
    """The system under test, imported from the checkout's ``src``."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.configs import get_config
    from repro.data.requests import Request
    from repro.kernels._interpret import ENV_VAR as INTERPRET_ENV
    from repro.kernels._interpret import resolve_interpret
    from repro.models.api import get_model
    from repro.runtime.serving import EngineConfig, ServingEngine
    from repro.runtime.tiered_kv import ROLE_DECODE

    return types.SimpleNamespace(**locals())


class Compiles(logging.Handler):
    """Counts the executables JAX builds: in all since ``install``, and
    between ``begin`` and ``end`` with the name and shapes JAX logs for
    each."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _installed = None

    def __init__(self):
        super().__init__()
        self._active = False
        self.lowered = 0  # between begin and end
        self.lowered_total = 0  # since install
        self.names: list = []

    def begin(self):
        import jax

        self.lowered = 0
        self.names = []
        self._active = True
        jax.config.update("jax_log_compiles", True)

    def end(self):
        import jax

        self._active = False
        jax.config.update("jax_log_compiles", False)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered_total += 1
            self.lowered += self._active

    def emit(self, record):
        msg = record.getMessage()
        if self._active and msg.startswith("Compiling "):
            self.names.append(msg.split(". Argument mapping", 1)[0][len("Compiling "):])

    @classmethod
    def install(cls) -> "Compiles":
        """One counter per process: JAX keeps its listeners for good."""
        if cls._installed is None:
            import jax

            c = cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(c._on)
            logger = logging.getLogger("jax")
            for h in logger.handlers:  # keep the window's compile log off stderr
                h.addFilter(lambda record: not c._active)
            logger.addHandler(c)
        return cls._installed


@dataclasses.dataclass
class Served:
    rid: int
    prompt: np.ndarray
    due: float
    granted: int  # tokens owed: the first token plus one per decode step
    tokens: list = dataclasses.field(default_factory=list)
    stamps: list = dataclasses.field(default_factory=list)
    done: bool = False
    bad_tokens: int = 0


def granted_tokens(prompt_len: int, output: int, max_len: int) -> int:
    """Tokens the engine owes a request: its first token, then one per
    granted decode step (the prompt cut to ``max_len - 2``, the decode
    budget to what ``max_len`` leaves)."""
    prompt = min(prompt_len, max(1, max_len - 2))
    return 1 + max(1, min(output, max_len - prompt - 1))


class Loop:
    """The serving loop: submit what has arrived, step, deliver."""

    def __init__(self, prog, eng, source, *, vocab: int, clock=time.perf_counter,
                 annotate=None):
        self.prog, self.eng, self.source = prog, eng, source
        self.vocab = vocab
        self.clock = clock
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.served: dict = {}
        self.n_finished = 0
        self.steps = self.chunk_steps = 0
        self.step_host_s = 0.0
        self.step_wall = {False: [0, 0.0], True: [0, 0.0]}  # decode, chunk: steps, seconds
        self.step_log = None  # per-step work, kept while tracing
        self.lookups = None  # per-call (near, far), kept while tracing
        self.ids_handed = 0
        self.capture = None  # filled by one lookup when armed
        self._wrap_lookup()

    def _wrap_lookup(self):
        store = self.eng.tiered
        inner = store.lookup_segments

        def counted(page_ids, seg_of, n_segments, slot_idx=None, tenant_idx=None,
                    role_idx=None):
            ids = np.asarray(page_ids, np.int64).reshape(-1)
            self.ids_handed += ids.size
            rows = inner(page_ids, seg_of, n_segments, slot_idx=slot_idx,
                         tenant_idx=tenant_idx, role_idx=role_idx)
            if self.lookups is not None:
                far = int(np.count_nonzero(store.tier_host[ids] == 1))
                self.lookups.append((ids.size - far, far))
            if self.capture is not None and not self.capture:
                slots = self.eng.slots
                self.capture.update(
                    rows=rows, ids=ids, seg_of=np.asarray(seg_of).reshape(-1),
                    far=store.tier_host[ids] == 1,
                    segments=[(i, slots[i].seq_id, r,
                               self.eng.pagetable.seq_len[slots[i].seq_id])
                              for i, r in zip(slot_idx, role_idx)])
            return rows

        store.lookup_segments = counted

    def submit(self, reqs):
        max_len = self.eng.ecfg.max_len
        for r in reqs:
            self.eng.submit(self.prog.Request(r.rid, r.prompt, r.output, -1, r.due))
            self.served[r.rid] = Served(r.rid, r.prompt, r.due,
                                        granted_tokens(len(r.prompt), r.output, max_len))

    def _deliver(self, rid: int, tok: int, t: float):
        s = self.served[rid]
        if not 0 <= tok < self.vocab:
            s.bad_tokens += 1
        s.tokens.append(tok)
        s.stamps.append(t)

    def once(self) -> bool:
        """One step boundary. Returns False when nothing was running."""
        eng = self.eng
        begin = self.clock()
        with self.annotate("bench.submit"):
            fin = eng.finished[self.n_finished:]
            self.n_finished += len(fin)
            for rid in fin:
                self.served[rid].done = True
            self.submit(self.source.poll(self.clock(), len(fin)))
        decoding = {i: s.seq_id for i, s in enumerate(eng.slots)
                    if s.active and not s.prefilling}
        lengths = ({i: eng.pagetable.seq_len[rid] for i, rid in decoding.items()}
                   if self.step_log is not None else None)
        before = eng.model_dispatches
        with self.annotate("bench.step"):
            a = self.clock()
            eng.step()
            b = self.clock()
        if eng.model_dispatches == before:
            return False
        with self.annotate("bench.readback"):
            toks = np.asarray(eng.next_tokens)
            t = self.clock()
        chunks = eng._step_chunks
        self.steps += 1
        self.chunk_steps += bool(chunks)
        self.step_host_s += b - a
        wall = self.step_wall[bool(chunks)]
        wall[0] += 1
        wall[1] += t - begin
        for i, rid in decoding.items():
            self._deliver(rid, int(toks[i]), t)
        for i in chunks:
            s = eng.slots[i]
            if s.active and s.chunk is None:  # its prompt completed this step
                self._deliver(s.seq_id, int(toks[i]), t)
        if self.step_log is not None:
            ctx = sum(n + 1 for n in lengths.values())
            n_tok = len(lengths)
            for start, end in chunks.values():
                n_tok += end - start
                ctx += dense_lm.span_context_sum(start, end)
            self.step_log.append((n_tok, ctx, bool(chunks)))
        return True

    def idle(self):
        time.sleep(0.001)  # nothing admitted yet: let the generator thread run


def check_model(conf: dict, cfg, api, params):
    """Fail loudly where the program's model or its weight layout differs
    from the configuration file."""
    want = {
        "d_model": conf["hidden_size"], "n_layers": conf["num_hidden_layers"],
        "n_heads": conf["num_attention_heads"], "n_kv_heads": conf["num_key_value_heads"],
        "d_ff": conf["intermediate_size"], "vocab_size": conf["vocab_size"],
        "rope_theta": conf["rope_theta"], "tie_embeddings": conf["tie_word_embeddings"],
        "norm_eps": conf["rms_norm_eps"],
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"bench: the program's {cfg.name} is {got}, the configuration says {want}")
    import jax

    ours = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    theirs = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), api.abstract_params())
    if ours != theirs:
        raise SystemExit(f"bench: weight layout {ours} is not the program's {theirs}")


def build(prog, conf: dict, ref, seed: int, cfg=None):
    cfg = cfg or prog.get_config(conf["registry_name"])
    cfg = dataclasses.replace(cfg, norm_eps=conf["rms_norm_eps"])  # as published
    api = prog.get_model(cfg)
    params = ref.make(conf, seed)
    check_model(conf, cfg, api, params)
    eng = prog.ServingEngine(api, params, prog.EngineConfig(**conf["engine"]), seed=seed)
    return eng


def _in_threads(fn, jobs, threads: int):
    """``fn(job)`` for every job, over ``threads`` workers. Compiling a
    small executable is mostly host work that JAX does outside the GIL, so
    the workers build them side by side."""
    def run(job):
        fn(job)  # keep no result: a future holds what its call returns

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        for f in [pool.submit(run, j) for j in jobs]:
            f.result()


def warm(eng, max_ids: int, log=lambda msg: None, threads: int = 6):
    """Build every executable this cell's steps dispatch, and leave the
    engine as it was: both model steps and the slot reset on the engine's
    own buffers; the tiered-gather kernel at every id bucket up to
    ``max_ids`` on the engine's store, and the cut of its padded rows at
    every id count; the tiered write path at every split of a step's pages
    between the tiers, and a placement migration of every size, on scratch
    stores that the engine builds as it builds its own. The engine makes the
    small executables from the shapes it meets, so without this they would
    compile inside the window."""
    import jax.numpy as jnp

    compiles = Compiles.install()
    mark = [time.perf_counter(), compiles.lowered_total]

    def done(phase):
        t, lo = time.perf_counter(), compiles.lowered_total
        log(f"warm {phase}: {t - mark[0]:.3f} s, {lo - mark[1]} executables built")
        mark[:] = [t, lo]

    e = eng.ecfg
    b, c = e.max_batch, e.prefill_chunk
    eng.next_tokens, eng.cache = eng._decode(eng.params, eng.cache, eng.next_tokens[:, None])
    z = jnp.zeros((b, c), bool)
    eng.next_tokens, eng.cache = eng._chunk_decode(
        eng.params, eng.cache, eng.next_tokens, jnp.zeros((b, c), jnp.int32), z, z, z)
    for i in range(b):  # zero every slot again
        eng.cache = eng._reset_slot_jit(eng.cache, jnp.int32(i))
    eng.next_tokens = jnp.zeros_like(eng.next_tokens)
    jax_block(eng)
    done("model steps and slot reset")

    _warm_tier_plane(eng, max_ids, threads)
    done(f"tier plane: gathers and row cuts up to {max_ids} ids, writes of 1 to {b} pages, "
         "migrations")


def _warm_tier_plane(eng, max_ids: int, threads: int):
    import jax

    b = eng.ecfg.max_batch
    store = eng.tiered
    cuts, n = [], 32
    while True:
        rows = store.lookup_segments(np.zeros(n, np.int64), np.zeros(n, np.int32), b + 1,
                                     slot_idx=[0], tenant_idx=[0], role_idx=[0])
        cuts += [(rows, size) for size in range(n // 2 + 1 if n > 32 else 1, min(n, max_ids + 1))]
        if n >= max_ids:
            break
        n *= 2
    _in_threads(lambda job: job[0][: job[1]].block_until_ready(), cuts, threads)
    store.drain_counters(discard=True)
    del cuts, rows

    # pages are rewritten when a sequence fills them, before it reads them;
    # a placement epoch swaps m far pages with m near ones, m up to half the
    # placement's migration budget
    near = np.flatnonzero(store.tier_host == 0)
    far = np.flatnonzero(store.tier_host == 1)
    budget = min(eng.placement.migrate_budget // 2, near.size, far.size)
    jobs = [("write", n, k) for n in range(1, b + 1) for k in range(n + 1)]
    jobs += [("migrate", m, 0) for m in range(1, budget + 1)]
    workers = max(1, threads // 2)  # each holds a scratch store

    def work(part):
        scratch = eng._make_tiered_store()
        scratch.migrate(eng.placement.near_blocks(), account=False)
        for kind, n, k in part:
            if kind == "write":
                pages = np.concatenate([near[:k], far[: n - k]])
                scratch.write(pages, eng._payload_rows(eng.cache, [0] * n, [0] * n, pages))
            else:
                scratch.migrate(np.concatenate([near[n:], far[:n]]), account=False)
                scratch.migrate(near, account=False)
        jax.block_until_ready((scratch.near, scratch.far_q, scratch.flat))

    _in_threads(work, [jobs[i::workers] for i in range(workers)], workers)


def jax_block(eng):
    import jax

    jax.block_until_ready((eng.cache, eng.next_tokens, eng.tiered.near, eng.tiered.far_q,
                           eng.tiered.flat))


def fill(loop: Loop, clock):
    """Set-up the traffic needs: submit the first requests and step until
    no slot is still prefilling, so the window starts in decode."""
    loop.submit(loop.source.setup(clock()))
    while loop.eng.queue or any(s.prefilling for s in loop.eng.slots):
        loop.once()


def _drained(eng):
    eng.drain_tier_counters()
    return eng.role_hits.sum(axis=0).copy()  # (near, far)


def window(loop: Loop, seconds: float, *, trace_dir=None, compiles=None):
    """The timed window, ``(t0, t0 + seconds]``. Returns its readings."""
    import jax

    eng, clock = loop.eng, loop.clock
    hits0 = _drained(eng)
    loop.ids_handed = 0
    loop.steps = loop.chunk_steps = 0
    loop.step_host_s = 0.0
    loop.step_wall = {False: [0, 0.0], True: [0, 0.0]}
    tracing = trace_dir is not None
    traced = (None, None)
    if tracing:
        loop.step_log, loop.lookups = [], []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    if compiles is not None:
        compiles.begin()
    t0 = clock()
    loop.source.start(t0)
    deadline = t0 + seconds
    t = t0
    while t < deadline:
        if not loop.once():
            loop.idle()
        t = clock()
        if tracing and t >= t0 + TRACE_LIMIT_S:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            traced = (loop.step_log, loop.lookups)
            loop.step_log = loop.lookups = None
    if compiles is not None:
        compiles.end()
    t1 = deadline
    if tracing:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = (loop.step_log, loop.lookups)
        loop.step_log = loop.lookups = None
    loop.source.stop()
    queued = len(eng.queue)
    steps = (loop.steps, loop.chunk_steps, loop.step_host_s, loop.ids_handed)
    hits = _drained(eng) - hits0
    return {
        "t0": t0, "t1": t1, "steps": steps[0], "chunk_steps": steps[1],
        "step_host_s": steps[2], "ids_handed": steps[3],
        "near_hits": int(hits[0]), "far_hits": int(hits[1]),
        "step_log": traced[0], "lookups": traced[1], "queued": queued,
        "step_wall": {kind: tuple(v) for kind, v in loop.step_wall.items()},
    }


def capture_rows(loop: Loop, gen: np.random.Generator) -> list:
    """One more step after the window closed, on the same engine and
    sizes: keep a seeded sample of the rows its tier-plane lookup returned
    for decode segments, with the (request, position) each row must hold and
    whether it came from the far tier. Requests that have far rows are
    picked first, and their far rows first, so that the far tier's int8
    rows are compared wherever the step read any."""
    loop.capture = {}
    while not loop.capture:
        if not loop.once():
            break
    cap, loop.capture = loop.capture, None
    if not cap:
        return []
    ps = loop.eng.ecfg.page_size
    decode = [(k, rid, length) for k, (i, rid, role, length) in enumerate(cap["segments"])
              if role == loop.prog.ROLE_DECODE]
    where = [np.flatnonzero(cap["seg_of"] == k) for k, _, _ in decode]
    order = list(gen.permutation(len(decode)))
    has_far = [j for j in order if cap["far"][where[j]].any()][: ROW_REQUESTS // 2]
    picked = has_far + [j for j in order if j not in has_far][: ROW_REQUESTS - len(has_far)]
    want = []
    for j in picked:
        _, rid, length = decode[j]
        w = where[j]
        far = gen.permutation(w[cap["far"][w]])[:FAR_ROWS_PER_REQUEST]
        near = gen.permutation(w[~cap["far"][w]])[: ROWS_PER_REQUEST - far.size]
        for r in np.sort(np.concatenate([far, near])):
            page = int(r - w[0])
            want.append((rid, min((page + 1) * ps, length) - 1, int(r), bool(cap["far"][r])))
    import jax.numpy as jnp

    rows = np.asarray(cap["rows"][jnp.asarray([r for _, _, r, _ in want])])
    return [(rid, pos, rows[n], far) for n, (rid, pos, _, far) in enumerate(want)]


def pick_requests(served: dict, rows: list, gen: np.random.Generator) -> list:
    """The requests the reference reads: those whose rows were captured,
    the finished one with most tokens, then a seeded draw of the rest until
    some hundreds of served tokens are covered."""
    have = [s for s in served.values() if s.tokens]
    picked = {row[0] for row in rows}
    done = [s for s in have if s.done]
    if done:
        picked.add(max(done, key=lambda s: len(s.tokens)).rid)
    for k in gen.permutation(len(have)):
        if (len(picked) >= MAX_REF_REQUESTS
                or sum(len(served[r].tokens) for r in picked) >= MIN_REF_TOKENS):
            break
        picked.add(have[k].rid)
    return sorted(picked)


def compare(ref, conf: dict, seed: int, served: dict, picked: list, rows: list,
            max_len: int, controls=()) -> dict:
    """Run the reference over each picked request (its prompt and served
    tokens, teacher-forced) and read the two model numbers. Each of
    ``controls`` (a lower precision) is read the same way in the program's
    place: the gap of the token it puts first, and its KV rows."""
    import jax.numpy as jnp

    params = ref.make(conf, seed)
    kw = ref.static_args(conf)
    names = ("program",) + tuple(controls)
    gap = dict.fromkeys(names, 0.0)
    row_err = dict.fromkeys(names, 0.0)
    compared = 0
    for rid in picked:
        s = served[rid]
        seq = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
        tokens = np.zeros(max_len, np.int32)
        tokens[: seq.size] = seq
        targets = np.zeros(max_len, np.int32)
        p = s.prompt.size
        span = slice(p - 1, p - 1 + len(s.tokens))
        targets[span] = s.tokens
        mine = [(pos, row) for r, pos, row, _ in rows if r == rid]
        pos = np.zeros(ROWS_PER_REQUEST, np.int32)
        pos[: len(mine)] = [m[0] for m in mine]
        tokens, pos = jnp.asarray(tokens), jnp.asarray(pos)
        gaps, want = ref.check(params, tokens, jnp.asarray(targets), pos, **kw)
        want = np.asarray(want)
        got = {"program": (np.asarray(gaps)[span], [row for _, row in mine])}
        for q in controls:
            g, r = ref.control(params, tokens, pos, quant=q, **kw)
            got[q] = (np.asarray(g)[span], list(np.asarray(r)[: len(mine)]))
        compared += len(s.tokens)
        for name, (g, rs) in got.items():
            gap[name] = max(gap[name], float(g.max()))
            for n, row in enumerate(rs):
                err = float(np.abs(row - want[n]).max()) / float(np.abs(want[n]).max())
                row_err[name] = max(row_err[name], err)
    out = {"logit_gap": gap["program"], "tokens_compared": compared,
           "kv_row_err": row_err["program"], "rows_compared": len(rows),
           "far_rows_compared": sum(far for *_, far in rows)}
    if controls:
        out["control"] = {q: {"logit_gap": gap[q], "kv_row_err": row_err[q]} for q in controls}
    return out


def judge(got: dict, hits_minus_ids: int, misses: int, limits: dict):
    """The numbers compared, each beside its limit, and whether all hold.
    ``got`` holds the two model numbers, of the program or of a control put
    in its place."""
    checks = {
        "logit_gap": {"value": got["logit_gap"], "limit": limits["logit_gap"]},
        "kv_row_err": {"value": got["kv_row_err"], "limit": limits["kv_row_err"]},
        "hits_minus_ids": {"value": hits_minus_ids, "limit": 0},
        "delivery_misses": {"value": misses, "limit": 0},
    }
    correct = (
        got["tokens_compared"] > 0 and got["rows_compared"] > 0
        and all(abs(c["value"]) <= c["limit"] for c in checks.values())
    )
    return checks, bool(correct)


def compile_cache():
    """JAX's persistent compilation cache, in the checkout at a fixed path
    whatever the environment names, with no size limit (a limited cache
    takes a file lock on every read and write) and every program kept, the
    tier plane's small ones too, so that only a checkout's first run
    compiles."""
    import jax
    from jax._src import compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()


def run_cell(cell_name: str, seed: int, seconds: float, *, trace: bool, out_dir,
             log=print) -> dict:
    """One run of one cell on the chip. Returns the object the entry point prints."""
    t_start = time.perf_counter()
    prog = program()
    bench = spec.benchmark()
    cell = spec.cell(cell_name, bench)
    require_tpu(prog, cell["chips"])
    compile_cache()
    return run(prog, cell, spec.config(cell["config"]), spec.traffic(cell["traffic"]),
               spec.limits(cell_name), seed, seconds, trace=trace, out_dir=out_dir,
               t_start=t_start, log=log)


def run(prog, cell: dict, conf: dict, mix: dict, limits: dict, seed: int, seconds: float,
        *, trace: bool, out_dir, t_start: float, log=print, model_cfg=None,
        peaks: dict = None, controls=()) -> dict:
    """Set-up, window, check. ``model_cfg`` stands in for the registry's
    model (a rehearsal passes a reduced one); ``peaks`` for the table's;
    ``controls`` adds the lower precisions' readings (``bench/control.py``)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    peaks = peaks or spec.peaks(dev.device_kind)
    compiles = Compiles.install()
    ref = spec.reference(conf["reference"])
    eng = build(prog, conf, ref, seed, model_cfg)
    jax_block(eng)
    log(f"process start to weights and engine built: {time.perf_counter() - t_start:.3f} s")
    e = eng.ecfg
    max_ids = min(e.n_pages, e.max_batch * -(-e.max_len // e.page_size))
    warm(eng, max_ids, log)
    log(f"peak bytes after warm-up: {_peak(devices[: cell['chips']])}")
    source = spec.generator(mix["kind"]).make(
        mix, seed=seed, vocab=conf["vocab_size"], max_len=e.max_len, max_batch=e.max_batch)
    annotate = jax.profiler.TraceAnnotation if trace else None
    loop = Loop(prog, eng, source, vocab=conf["vocab_size"], annotate=annotate)
    t = time.perf_counter()
    fill(loop, time.perf_counter)
    jax_block(eng)
    setup_s = time.perf_counter() - t_start
    log(f"fill: {time.perf_counter() - t:.3f} s, {loop.steps} steps ({loop.chunk_steps} chunk)")
    w = window(loop, seconds, trace_dir=(out_dir / "trace") if trace else None,
               compiles=compiles)
    mem = dev.memory_stats() or {}
    peak_bytes = _peak(devices[: cell["chips"]])
    gen = np.random.default_rng([seed, 9])
    rows = capture_rows(loop, gen)
    served = loop.served
    t0, t1 = w["t0"], w["t1"]
    stamps = [s.stamps for s in served.values()]
    itl = stats.itl_gaps(stamps, t0, t1)
    ttft = stats.ttfts([(s.stamps[0], s.due) for s in served.values() if s.stamps], t0, t1)
    tokens = stats.tokens_in(stamps, t0, t1)
    in_window = [s for s in served.values() if s.due <= t1 and not (s.done and s.stamps[-1] <= t0)]
    misses = sum(1 for s in served.values()
                 if (s.done and len(s.tokens) != s.granted) or s.bad_tokens)
    log(f"window {t1 - t0:.3f} s: {w['steps']} steps ({w['chunk_steps']} chunk), "
        f"{tokens} tokens, {len(itl)} gaps, {len(ttft)} first tokens, "
        f"{len(in_window)} requests in flight or finished, {w['queued']} queued at the close")
    for kind, (n, sec) in (("decode", w["step_wall"][False]), ("chunk", w["step_wall"][True])):
        log(f"{kind} steps: {n}, {sec / max(n, 1) * 1e3:.3f} ms each on the host clock")
    log(f"compiles in the window: {compiles.lowered} executables built")
    for name in compiles.names:
        log(f"  compiled in the window: {name[:300]}")
    log(f"tier plane: near {w['near_hits']} far {w['far_hits']} ids handed {w['ids_handed']}")
    log(f"set-up {setup_s:.3f} s; peak bytes {peak_bytes}; bytes in use {mem.get('bytes_in_use')}")
    readings = {
        "window_s": t1 - t0, "tokens": tokens, "steps": w["steps"],
        "chunk_steps": w["chunk_steps"], "step_host_s": w["step_host_s"],
        "near_hits": w["near_hits"], "far_hits": w["far_hits"],
        "lateness_ms": list(getattr(source, "lateness_ms", [])),
        "peak_bytes": peak_bytes, "peaks": peaks, "model": conf,
        "step_log": w["step_log"], "lookups": w["lookups"],
        "itl_s": itl, "ttft_s": ttft, "setup_s": setup_s,
    }
    picked = pick_requests(served, rows, gen)
    max_len = e.max_len
    del eng, loop, source
    gc.collect()
    t_ref = time.perf_counter()
    got = compare(ref, conf, seed, served, picked, rows, max_len, controls)
    log(f"reference: {len(picked)} requests, {got['tokens_compared']} tokens, "
        f"{got['rows_compared']} rows ({got['far_rows_compared']} far) "
        f"in {time.perf_counter() - t_ref:.1f} s")
    hits_minus_ids = w["near_hits"] + w["far_hits"] - w["ids_handed"]
    checks, correct = judge(got, hits_minus_ids, misses, limits)
    readings["trace"] = None
    result = {
        "correct": bool(correct),
        "attempted": len(in_window),
        "failed": misses,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes),
        },
        "window_compiles": compiles.lowered,
    }
    if trace:
        from bench import trace as tracemod

        tr = tracemod.load(out_dir / "trace")
        readings["trace"] = tr
        result["device"]["busy_s"] = tracemod.busy_s(tr)
        result["device"]["window_s"] = tr.window_s
        metrics = per_layer(cell, readings)
        result["breakdown"] = tracemod.breakdown(tr)
    else:
        metrics = end_to_end(cell, readings)
    result["metrics"] = metrics
    result["compared"] = {"tokens": got["tokens_compared"], "rows": got["rows_compared"],
                          "far_rows": got["far_rows_compared"]}
    if controls:  # each control judged as the program is, in its place
        result["control"] = {}
        for q, c in got["control"].items():
            c_checks, c_correct = judge(dict(got, **c), hits_minus_ids, misses, limits)
            result["control"][q] = {"correct": c_correct, "checks": c_checks}
    result["checks"] = checks
    return result


def end_to_end(cell: dict, r: dict) -> dict:
    values = {
        "output_tokens_per_s": lambda: r["tokens"] / r["window_s"],
        "itl_p50_ms": lambda: stats.percentile(r["itl_s"], 50) * 1e3,
        "itl_p99_ms": lambda: stats.percentile(r["itl_s"], 99) * 1e3,
        "setup_s": lambda: r["setup_s"],
    }
    return {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]} for m in cell["end_to_end"]}


def per_layer(cell: dict, r: dict) -> dict:
    out = {}
    for m in cell["per_layer"]:
        v = spec.metric_reader(m["name"])(r)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def require_tpu(prog, chips: int):
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench: JAX backend is {backend!r}, not 'tpu'; the benchmark runs only on a TPU")
    if prog.resolve_interpret(None):
        raise SystemExit(f"bench: {prog.INTERPRET_ENV} forces interpret-mode kernels; unset it")
    n = len(jax.devices())
    if n < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds {n}")
