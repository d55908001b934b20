"""Open loop: requests arrive on a schedule whether or not earlier ones
have finished, as independent users send them.

Gaps between arrivals are exponential at ``rate_per_s`` (stratified per
block and ordered, see ``_draws``). A generator thread pushes each
request into a queue at its due time and notes how late it was; the serving loop takes
what has arrived at each step boundary. A request's latency counts from
its due time, so a stall that delays later submissions is charged to them.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np

from bench.traffic import _draws


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray  # int32 token ids
    output: int  # output tokens asked for
    due: float  # perf_counter time it was due


def lengths(mix: dict, seed: int, max_len: int, n: int):
    """``n`` (prompt, output) lengths, each clipped to the mix's bounds and
    the output to what ``max_len`` leaves after its prompt."""
    p = mix["prompt"]
    o = mix["output"]
    prompts = _draws.ordered_blocks(
        _draws.lognormal_set(**p, block=mix["block"]), n, _draws.rng(seed, 1))
    outputs = _draws.ordered_blocks(
        _draws.lognormal_set(**o, block=mix["block"]), n, _draws.rng(seed, 2), _draws.SILVER)
    return prompts, np.minimum(outputs, max_len - prompts - 1)


class OpenLoop:
    def __init__(self, mix: dict, *, seed: int, vocab: int, max_len: int, max_batch: int,
                 horizon_s: float = 600.0):
        rate = float(mix["rate_per_s"])
        n = int(rate * horizon_s) + mix["block"]
        gaps = _draws.ordered_blocks(
            _draws.exponential_set(1.0 / rate, mix["block"]), n, _draws.rng(seed, 5))
        self.offsets = np.cumsum(gaps)
        self.prompts, self.outputs = lengths(mix, seed, max_len, n)
        self.tokens = _draws.rng(seed, 4)
        self.vocab = vocab
        self.arrived: "queue.Queue[Req]" = queue.Queue()
        self.lateness_ms: list = []
        self._stop = threading.Event()
        self._thread = None

    def setup(self, now: float) -> list:
        return []

    def _push(self, t0: float):
        for i, off in enumerate(self.offsets):
            # draw the prompt before its due time, so the push itself is cheap
            req = Req(i, _draws.token_ids(self.tokens, self.prompts[i], self.vocab),
                      int(self.outputs[i]), t0 + float(off))
            wait = req.due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            self.arrived.put(req)
            self.lateness_ms.append((time.perf_counter() - req.due) * 1e3)

    def start(self, t0: float):
        self._thread = threading.Thread(target=self._push, args=(t0,), daemon=True)
        self._thread.start()

    def poll(self, now: float, finished: int) -> list:
        out = []
        while True:
            try:
                out.append(self.arrived.get_nowait())
            except queue.Empty:
                return out

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                raise RuntimeError("the open-loop generator thread did not stop")


def make(mix: dict, **kw) -> OpenLoop:
    return OpenLoop(mix, **kw)
