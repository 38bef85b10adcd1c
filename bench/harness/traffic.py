"""One generator for every traffic mix.  A mix is a data file under
``bench/traffic``; this module turns it and ``--seed`` into the inputs the
program receives, and nothing else.

Sizes and gaps are drawn from the mix's own ``base_seed``, and ``--seed``
draws the token ids they carry.  Every seed therefore gets the same work:
an open-loop schedule is the same trace of arrivals, sizes and sessions
on every seed, since at a fixed load the order of arrivals alone moves
the tails by far more than two runs of one order differ.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class RequestSpec:
    """One serving request: when it is due (seconds after the window
    opens, or before it for lead-in traffic), its prompt and its answer
    length."""

    due_s: float
    prompt: list[int]
    max_new_tokens: int
    session: int = -1


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *salt])


def lognormal_lengths(spec: dict, n: int, rng: np.random.Generator
                      ) -> np.ndarray:
    """``n`` integer lengths drawn log-normal around ``spec['median']``
    with shape ``spec['sigma']``, clipped to ``[min, max]``."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _poisson_gaps(rate: float, n: int, span_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential gaps at ``rate``, scaled so that they fill
    ``span_s`` exactly: a Poisson process conditioned on its count."""
    gaps = rng.exponential(1.0 / rate, size=n)
    return gaps * (span_s / gaps.sum())


def session_schedule(mix: dict, seed: int, seconds: float, vocab: int
                     ) -> tuple[list[RequestSpec], list[RequestSpec],
                                list[RequestSpec], list[RequestSpec]]:
    """Open-loop requests from ``mix['sessions']`` sessions, each with its
    own context that every request of the session shares, plus a suffix
    of its own.

    Returns ``(contexts, warm, lead_in, window)``: one request per session
    that fills the prefix cache at set-up; ``mix['warm_requests']`` more,
    dealt over the sessions with suffix lengths spread evenly over their
    range, so that set-up meets every number of suffix pages a request
    can bring; the requests of the lead-in (due in ``[-lead_in_s, 0)``);
    and the requests due in ``[0, seconds)``."""
    base = _rng(mix["base_seed"])
    mine = _rng(seed, 1)
    n_sess = mix["sessions"]
    ctx = [mine.integers(0, vocab, size=mix["context_tokens"]).tolist()
           for _ in range(n_sess)]

    def request(due: float, s: int, suffix: int, answer: int) -> RequestSpec:
        tail = mine.integers(0, vocab, size=int(suffix)).tolist()
        return RequestSpec(float(due), ctx[s] + tail, int(answer), s)

    def requests(span_s: float, offset_s: float, salt: int):
        n = max(1, int(round(mix["rate_per_s"] * span_s)))
        b = _rng(mix["base_seed"], salt)
        gaps = _poisson_gaps(mix["rate_per_s"], n, span_s, b)
        suffix = lognormal_lengths(mix["suffix"], n, b)
        answer = lognormal_lengths(mix["answer"], n, b)
        session = b.integers(0, n_sess, size=n)
        due = offset_s + np.cumsum(gaps) - gaps[0]
        return [request(due[i], int(session[i]), suffix[i], answer[i])
                for i in range(n)]

    first = lognormal_lengths(mix["suffix"], n_sess, base)
    contexts = [request(0.0, s, first[s], mix["context_answer"])
                for s in range(n_sess)]
    spread = np.rint(np.linspace(mix["suffix"]["min"], mix["suffix"]["max"],
                                 mix["warm_requests"]))
    warm = [request(0.0, k % n_sess, n, mix["context_answer"])
            for k, n in enumerate(spread)]
    lead = mix["lead_in_s"]
    lead_in = requests(lead, -lead, 2) if lead > 0 else []
    return contexts, warm, lead_in, requests(seconds, 0.0, 3)
