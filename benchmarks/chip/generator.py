"""The one traffic generator: turns a mix file (``traffic/<mix>.json``)
and a seed into requests.

Every seed gets the same sequence of prompt and output lengths: request
i's length is the mix's distribution read at the quantile
``frac(0.5 + i * a)``, ``a`` an irrational step of its own for each
length (the golden-ratio sequence), so that any run of consecutive
requests covers the distribution evenly.  The seed chooses the tokens
(and the documents' tokens).  Runs with different seeds therefore do the
same work on other data: with the lengths drawn in a random order, the
few dozen requests a window serves made `prompt_tok_s` of two seeds
differ by 18% (on a TPU v5e).

A mix file holds:

- ``loop``: ``"closed"`` (each client sends its next request when the
  last one finished) or ``"open"`` (requests arrive on a schedule,
  whatever the server does);
- ``clients``: closed-loop clients;
- ``arrivals`` (open loop): ``{"kind": "poisson", "rate_per_s": r}`` or
  ``{"kind": "bursty", "burst": k, "gap_s": g}``;
- ``prompt``, ``output``: ``{"median", "sigma", "min", "max"}`` of a
  clipped lognormal, in tokens;
- ``documents`` (optional): ``{"count", "min", "max", "zipf_s",
  "question_min", "question_max"}``; a prompt is then one document,
  chosen with Zipf popularity, followed by a question of its own, and
  ``prompt`` is not used;
- ``requests``: how many requests the mix can send;
- ``warmup``: ``{"steps": n}`` (serve n steps before the window) or
  ``{"finished": n}`` (serve until n requests have finished); the
  window opens at the start of the next round;
- ``why``: one line on what the mix stands for.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def poisson_arrivals(n: int, mean_gap_s: float, rng) -> List[float]:
    """Seeded open-loop Poisson process: exponential interarrivals
    (copied from ``benchmarks/serve_bench.py``)."""
    return list(np.cumsum(rng.exponential(mean_gap_s, size=n)))


def bursty_arrivals(n: int, burst: int, gap_s: float, rng) -> List[float]:
    """Bursts of ``burst`` near-simultaneous arrivals, ``gap_s`` apart,
    with ~0.1 ms in-burst jitter (copied from
    ``benchmarks/serve_bench.py``)."""
    out = []
    for i in range(n):
        out.append((i // burst) * gap_s + rng.uniform(0, 1e-4))
    return sorted(out)


STEPS = {"prompt": (5 ** 0.5 - 1) / 2, "output": 2 ** 0.5 - 1,
         "question": 3 ** 0.5 - 1, "document": math.pi - 3,
         "choice": math.e - 2}


def _quantiles(what: str, n: int) -> np.ndarray:
    """The evenly spread quantiles ``frac(0.5 + i * a)`` for i < n."""
    return np.mod(0.5 + np.arange(n) * STEPS[what], 1.0)


def _lognormal(spec: Dict, q: np.ndarray) -> np.ndarray:
    """The clipped lognormal ``spec`` at quantiles ``q``."""
    nd = NormalDist()
    vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(p))
            for p in q]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _uniform(lo: int, hi: int, q: np.ndarray) -> np.ndarray:
    return np.rint(lo + (hi - lo) * q).astype(np.int64)


def _zipf(count: int, s: float, q: np.ndarray) -> np.ndarray:
    """Document ranks of Zipf(s) popularity over ``count`` at quantiles q."""
    w = 1.0 / np.arange(1, count + 1) ** s
    return np.searchsorted(np.cumsum(w) / w.sum(), q, side="right")


class Traffic:
    """Requests of one mix for one seed, handed out in order of demand."""

    def __init__(self, mix: Dict, seed: int, vocab: int, page: int):
        self.mix = mix
        self.vocab = vocab
        n = mix["requests"]
        rng = np.random.default_rng(seed)
        self._rng = rng
        self.seed = seed
        self.outputs = _lognormal(mix["output"], _quantiles("output", n))
        docs = mix.get("documents")
        if docs:
            k = docs["count"]
            lens = _uniform(docs["min"], docs["max"], _quantiles("document", k))
            lens = (lens // page) * page               # whole pages
            self.documents = [rng.integers(0, vocab, int(ln)) for ln in lens]
            self.doc_of = _zipf(k, docs["zipf_s"], _quantiles("choice", n))
            self.questions = _uniform(docs["question_min"],
                                      docs["question_max"],
                                      _quantiles("question", n))
            self.prompts = None
        else:
            self.documents = None
            self.prompts = _lognormal(mix["prompt"], _quantiles("prompt", n))
        self.sent = 0

    def next(self) -> Dict:
        """The next request: ``{"rid", "prompt", "max_new", "doc"}``."""
        i = self.sent
        if i >= self.mix["requests"]:
            raise RuntimeError(f"the mix's {self.mix['requests']} requests "
                               "ran out: raise 'requests' in the mix file")
        self.sent += 1
        rng = self._rng
        if self.documents is not None:
            doc = int(self.doc_of[i])
            q = rng.integers(0, self.vocab, int(self.questions[i]))
            prompt = np.concatenate([self.documents[doc], q])
        else:
            doc = -1
            prompt = rng.integers(0, self.vocab, int(self.prompts[i]))
        return {"rid": i, "prompt": prompt, "max_new": int(self.outputs[i]),
                "doc": doc}

    def arrivals(self) -> List[float]:
        """Arrival times of every request of an open-loop mix, in seconds
        from the start of serving."""
        a = self.mix["arrivals"]
        rng = np.random.default_rng([self.seed, 3])
        n = self.mix["requests"]
        if a["kind"] == "poisson":
            return poisson_arrivals(n, 1.0 / a["rate_per_s"], rng)
        if a["kind"] == "bursty":
            return bursty_arrivals(n, a["burst"], a["gap_s"], rng)
        raise ValueError(f"unknown arrivals {a['kind']!r}")

    def longest_fill(self) -> int:
        """The most positions any request of the mix can need."""
        if self.documents is not None:
            d = self.mix["documents"]
            p = max(len(x) for x in self.documents) + d["question_max"]
        else:
            p = self.mix["prompt"]["max"]
        return int(p + self.mix["output"]["max"])
