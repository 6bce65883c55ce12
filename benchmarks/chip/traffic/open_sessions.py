"""Open-loop chat sessions over shared system prompts (``kind:
open_sessions``).

Sessions arrive as a Poisson process at ``session_rate_per_s`` from the
window's start.  Each has ``turns`` [lo, hi] turns.  A turn's prompt is
the session's system prompt, the whole history (earlier user turns and
the tokens the system served for them) and a new user turn; the next
turn is due an exponential think time after the last token of this
one.  A session ends before a turn whose prompt and output would pass
``max_context`` tokens.  With ``shared: false`` every prompt is fresh
tokens of the same length, so nothing is shared or reused: the control
for the prefix cache, on the same draws of arrivals, turns and lengths.

Every seed gets the same sessions -- turn counts, system prompts,
lengths and think times from quantile grids -- arriving in the same
order on the same gaps, all assigned by a stream that does not depend on
the seed; the seed draws the tokens.  A window is shorter than many
sessions' lives, so an order that changed with the seed would change the
work inside the window (runs on six seeds that did so spread 12-15% in
tokens per second, against under 1% between two runs of one seed).  It
also gives every seed the same prompt lengths, whose prompt operations
compile once per length (see ``prompt_lengths``).
"""

from __future__ import annotations

import math
import os
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import draws  # noqa: E402
import numpy as np  # noqa: E402
from draws import Spec  # noqa: E402

#: stream ids of the draws: the sessions and their arrivals
#: (seed-independent), and the seed's tokens
_SIZES, _TOKENS = 0, 2


class OpenSessions:
    def __init__(self, params: Dict, *, seed: int, vocab: int,
                 seconds: float, max_batch: int):
        p = params
        self.vocab = vocab
        self.seconds = float(seconds)
        self.shared = bool(p.get("shared", True))
        self.max_context = int(p["max_context"])
        rate = float(p["session_rate_per_s"])
        n = max(int(math.ceil(rate * seconds)), 1)
        gs = draws.rng(0, _SIZES)
        t_lo, t_hi = p["turns"]
        turns = [t_lo + i % (t_hi - t_lo + 1) for i in range(n)]
        self.turns = draws.shuffled(gs, turns)
        sys_lens = [int(x) for x in p["system_prompts"]]
        counts = draws.zipf_counts(n, len(sys_lens), float(p["system_zipf_s"]))
        self.system_of = draws.shuffled(
            gs, [k for k, c in enumerate(counts) for _ in range(c)])
        m = sum(self.turns)
        u, o = p["user_len"], p["output_len"]
        self.user_lens = draws.shuffled(gs, draws.lognormal_sizes(
            m, u["median"], u["sigma"], u["min"], u["max"]))
        self.out_lens = draws.shuffled(gs, draws.lognormal_sizes(
            m, o["median"], o["sigma"], o["min"], o["max"]))
        self.thinks = draws.shuffled(
            gs, draws.exponential_gaps(m, float(p["think_mean_s"])))
        # turn k of session i reads slot first[i] + k of the per-turn draws
        self.first = [sum(self.turns[:i]) for i in range(n)]
        gaps = draws.shuffled(gs, draws.exponential_gaps(n, 1.0 / rate))
        self.starts = [float(t) for t in np.cumsum(gaps) - gaps[0]]
        gt = draws.rng(seed, _TOKENS)
        self.system = [draws.tokens(gt, L, vocab) for L in sys_lens]
        self._seed = seed

    # -- the harness's interface --------------------------------------------
    def prefixes(self) -> List[Tuple[int, ...]]:
        """Prompts to prefill in set-up, so the cache holds what a steady
        stream of sessions keeps there: the system prompts."""
        return list(self.system) if self.shared else []

    def setup_requests(self) -> List[Spec]:
        return []

    def arrivals(self) -> List[Tuple[float, Spec]]:
        out = []
        for i, t in enumerate(self.starts):
            spec = self._turn(i, 0, ())
            if spec is not None:
                out.append((t, spec))
        return out

    def on_finish(self, spec: Spec, output: List[int], at: float
                  ) -> List[Tuple[float, Spec]]:
        i, k = spec.meta["session"], spec.meta["turn"]
        if k + 1 >= self.turns[i]:
            return []
        nxt = self._turn(i, k + 1, spec.meta["context"] + tuple(output))
        if nxt is None:
            return []
        return [(at + float(self.thinks[self.first[i] + k]), nxt)]

    def prompt_lengths(self) -> List[int]:
        """Every prompt length a turn can have and still be due inside
        the window: a turn is due no sooner than its session's start plus
        the think times before it."""
        out = set()
        for i in range(len(self.starts)):
            ctx = len(self.system[self.system_of[i]])
            due = self.starts[i]
            for k in range(self.turns[i]):
                j = self.first[i] + k
                ctx += int(self.user_lens[j])
                if (ctx + int(self.out_lens[j]) > self.max_context
                        or due >= self.seconds):
                    break
                out.add(ctx)
                ctx += int(self.out_lens[j])
                due += float(self.thinks[j])
        return sorted(out)

    def max_tokens(self) -> int:
        """The longest prompt plus output any request may reach."""
        return self.max_context

    # -- helpers --------------------------------------------------------------
    def _turn(self, i: int, k: int, history: Tuple[int, ...]):
        """Turn ``k`` of session ``i``; ``history`` is the system prompt
        and the earlier turns as served (empty for the first turn)."""
        j = self.first[i] + k
        user_len, out_len = int(self.user_lens[j]), int(self.out_lens[j])
        g = draws.rng(self._seed, _TOKENS, i, k)
        if not history:
            history = self.system[self.system_of[i]]
        context = history + draws.tokens(g, user_len, self.vocab)
        if len(context) + out_len > self.max_context:
            return None
        prompt = (context if self.shared
                  else draws.tokens(g, len(context), self.vocab))
        return Spec(f"s{i}t{k}", prompt, out_len - 1,
                    {"session": i, "turn": k, "context": context})


def make(params: Dict, **kw) -> OpenSessions:
    return OpenSessions(params, **kw)
