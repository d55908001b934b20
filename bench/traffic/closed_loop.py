"""Closed loop: a fixed number of clients, each sending its next request
the moment its previous one completes (think time zero).

The first request of every client is submitted during set-up, with its
output length drawn as a residual life, so that completions are spread
through the window instead of arriving together, and its prompt from one
stratified set of as many sizes as there are clients. Each later request is
due at the delivery of the last token of the request it replaces.

The sizes, and the order in which they are sent, come from the mix's
``sizes_seed`` and are the same in every run. A closed loop admits only a
few requests in a window, and each admission costs as many chunk steps as
its prompt needs, so an order drawn from the run's seed would change the
window's work from seed to seed. The run's seed draws the token ids and
which slot holds which of the first requests, which leaves the work as it
is.
"""
from __future__ import annotations

from bench.traffic import _draws
from bench.traffic.open_loop import Req, lengths


class ClosedLoop:
    def __init__(self, mix: dict, *, seed: int, vocab: int, max_len: int, max_batch: int):
        self.clients = c = int(mix["clients_per_slot"] * max_batch)
        self.vocab = vocab
        sizes = int(mix["sizes_seed"])
        self.prompts, self.outputs = lengths(mix, sizes, max_len, 4 * c + 4096)
        # the first requests: one stratified set of ``c`` prompts, each paired
        # with a residual life, the pairs dealt to the slots in the seed's order
        first_prompts = _draws.lognormal_set(**mix["prompt"], block=c)
        first_outputs = _draws.residual_lives(
            _draws.lognormal_set(**mix["output"], block=mix["block"]), c, _draws.rng(sizes, 3),
        ).clip(1, max_len - first_prompts - 1)
        deal = _draws.rng(seed, 6).permutation(c)
        self.prompts[:c] = first_prompts[deal]
        self.outputs[:c] = first_outputs[deal]
        self.tokens = _draws.rng(seed, 4)
        self.next_rid = 0
        self.lateness_ms: list = []

    def _req(self, due: float) -> Req:
        i = self.next_rid
        self.next_rid += 1
        if i >= len(self.prompts):
            raise RuntimeError("closed loop ran past its drawn requests")
        return Req(i, _draws.token_ids(self.tokens, self.prompts[i], self.vocab),
                   int(self.outputs[i]), due)

    def setup(self, now: float) -> list:
        return [self._req(now) for _ in range(self.clients)]

    def start(self, t0: float):
        pass

    def poll(self, now: float, finished: int) -> list:
        return [self._req(now) for _ in range(finished)]

    def stop(self):
        pass


def make(mix: dict, **kw) -> ClosedLoop:
    return ClosedLoop(mix, **kw)
