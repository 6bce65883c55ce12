"""A closed loop of clients with no think time (``kind: closed_loop``).

``clients`` clients (``"max_batch"``: as many as the deployment batches)
each send a request, wait for its last token, and send the next at once,
``requests_per_client`` requests in all; size it so that a window ends
before the clients do (set-up warms every prompt length of it, each of
which compiles the runner's prompt operations once).  Prompts and
outputs are uniform on ``prompt_len`` and ``output_len``; nothing is
shared.

The clients start staggered, so the window sees requests at every stage
of progress and not one synchronized start: client ``c``'s first request
is taken ``progress`` of the way through (a quantile grid over the
clients, shuffled), the tokens it would already have served being part
of its prompt, so set-up prefills them and the window decodes the rest
at the context a steady stream would hold.

The sizes (quantile grids, assigned by a fixed stream) are the same for
every seed; the seed draws the tokens.  So every seed offers the same
work and the same prompt lengths, whose prompt operations compile once
per length.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import draws  # noqa: E402
from draws import Spec  # noqa: E402

_SIZES, _TOKENS = 0, 1
#: sizes drawn per client: the most requests a client can send
DRAWN = 9


class ClosedLoop:
    def __init__(self, params: Dict, *, seed: int, vocab: int,
                 seconds: float, max_batch: int):
        p = params
        c = p["clients"]
        self.clients = max_batch if c == "max_batch" else int(c)
        self.vocab = vocab
        self.max_total = int(p["max_total"])
        self.per_client = int(p["requests_per_client"])
        if not 1 <= self.per_client <= DRAWN:
            raise ValueError(f"requests_per_client {self.per_client}: "
                             f"1 to {DRAWN}")
        n = self.clients * DRAWN
        g = draws.rng(0, _SIZES)
        self.prompt_lens = draws.shuffled(g, draws.uniform_sizes(
            n, *p["prompt_len"]))
        self.out_lens = draws.shuffled(g, draws.uniform_sizes(
            n, *p["output_len"]))
        self.progress = draws.shuffled(g, draws.grid(self.clients))
        self.next_of = [0] * self.clients
        self._seed = seed

    def prefixes(self) -> List[Tuple[int, ...]]:
        return []

    def setup_requests(self) -> List[Spec]:
        """Each client's first request, part served already."""
        return [self._request(c, 0, float(self.progress[c]))
                for c in range(self.clients)]

    def arrivals(self) -> List[Tuple[float, Spec]]:
        return []

    def on_finish(self, spec: Spec, output: List[int], at: float
                  ) -> List[Tuple[float, Spec]]:
        c = spec.meta["client"]
        k = spec.meta["k"] + 1
        if k >= self.per_client:
            return []
        return [(at, self._request(c, k, 0.0))]

    def prompt_lengths(self) -> List[int]:
        """Prompt lengths of every request a client sends after its
        first (the first is prefilled in set-up)."""
        return sorted({len(self._request(c, k, 0.0).prompt)
                       for c in range(self.clients)
                       for k in range(1, self.per_client)})

    def max_tokens(self) -> int:
        return self.max_total

    def _request(self, c: int, k: int, progress: float) -> Spec:
        j = c * DRAWN + k
        prompt_len = int(self.prompt_lens[j])
        out_len = min(int(self.out_lens[j]), self.max_total - prompt_len)
        done = int(progress * (out_len - 1))
        g = draws.rng(self._seed, _TOKENS, c, k)
        return Spec(f"c{c}r{k}",
                    draws.tokens(g, prompt_len + done, self.vocab),
                    out_len - 1 - done, {"client": c, "k": k})


def make(params: Dict, **kw) -> ClosedLoop:
    return ClosedLoop(params, **kw)
