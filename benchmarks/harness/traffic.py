"""The one general traffic generator. A mix is a data file of
parameters (``traffic/<mix>.json``); this module turns a mix, a seed and
a count into requests.

Every seed gets the SAME requests in shape: the prompt lengths, output
lengths and arrival gaps are the evenly spaced quantiles of the mix's
distributions, laid out in ONE order (``LAYOUT_SEED``) — one fixed
realisation of the arrival process. The run's ``--seed`` draws the
token ids, the sampling seeds (and, in the runner, the weights). So a
seed can neither draw a heavier tail than another nor bunch the long
prompts differently. (With the order drawn from the run's seed, the
90th-percentile TTFT of 112 requests spread by 19 % of its median over
six seeds on the chip — the order was changing the work; PERF.md,
PR 24.)

(A fixed schedule makes a run nearly deterministic, so what noise is
left decides between whole trajectories: with a generator that woke
1 ms late the open-loop cell fell into one of two, 7 % apart in TTFT;
sent on time it repeats to 0.1 %. Moving each due instant by a
seed-drawn offset of under one engine round instead spread every
metric further. ``loadgen.run_open``; PERF.md, PR 24.)

Mix keys (all lengths in tokens):

    loop            "open" (arrivals on a schedule) | "closed" (clients)
    arrivals        {"process": "poisson"} (open loop; the rate is the
                    cell's, not the mix's)
    set_size        closed loop: how many distinct requests the run
                    cycles through
    prompt_tokens   {"dist": "lognormal", "median", "sigma", "min", "max"}
    output_tokens   the same, or {"dist": "uniform", "min", "max"}
    sampling        {"temperature", "top_p", "top_k"}; top_k 1 is greedy;
                    every request gets a sampling seed of its own
    prefix_sharing  {"groups": g, "shared_tokens": n}: each request opens
                    with one of g common n-token prefixes (0 = none; the
                    random ids then make every prompt unique, so no
                    prefix is ever shared)
    why             one line
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_FIRST_ID = 3          # ids 0..2 are pad/bos/eos in every vocab served
LAYOUT_SEED = 0        # draws the one order of lengths and gaps


@dataclasses.dataclass
class Request:
    uid: int
    prompt_ids: list
    max_tokens: int
    sampling_seed: int
    due_s: float | None = None     # open loop: seconds after window start


def quantiles(dist: dict, n: int) -> list:
    """The n evenly spaced quantiles ((i + 0.5) / n) of ``dist``, as
    whole numbers clipped to its min and max."""
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        z = NormalDist()
        raw = [math.exp(mu + sigma * z.inv_cdf(q)) for q in qs]
    elif dist["dist"] == "uniform":
        raw = [lo + (hi - lo) * q for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [min(hi, max(lo, int(round(x)))) for x in raw]


def arrival_gaps(arrivals: dict, rate: float, n: int) -> list:
    """n inter-arrival gaps (seconds) at mean rate ``rate``: evenly
    spaced quantiles of the process's gap distribution, rescaled so
    they sum to exactly n / rate."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


class Generator:
    """Requests for one run. ``count`` requests are laid out up front
    (open loop: with due times inside ``seconds``); ``next()`` hands
    them out in order and starts over, with new content, when a closed
    loop outruns the set."""

    def __init__(self, mix: dict, vocab_size: int, seed: int, *,
                 rate: float | None = None, seconds: float | None = None):
        self.mix, self.vocab = mix, int(vocab_size)
        self._rng = np.random.default_rng(int(seed))
        self._layout = np.random.default_rng([LAYOUT_SEED, 0x1a70])
        self._uid = 0
        self._queue: list = []
        if mix["loop"] == "open":
            if not rate or not seconds:
                raise ValueError("an open-loop mix needs a rate and a "
                                 "window length")
            self.count = max(1, int(round(rate * seconds)))
            gaps = arrival_gaps(mix["arrivals"], rate, self.count)
            order = self._layout.permutation(self.count)
            # the first request is due at the first gap; the last lands
            # inside the window because the gaps sum to count / rate
            due = np.cumsum([gaps[i] for i in order])
            self._due = (due * (seconds / max(seconds, due[-1] + 1e-9))
                         ).tolist()
        else:
            self.count = int(mix["set_size"])
            self._due = None
        self._prompt_lens = quantiles(mix["prompt_tokens"], self.count)
        self._output_lens = quantiles(mix["output_tokens"], self.count)
        share = mix.get("prefix_sharing") or {}
        self._groups = int(share.get("groups", 0))
        self._shared = int(share.get("shared_tokens", 0))
        self._prefixes = [
            np.random.default_rng([int(seed), g]).integers(
                _FIRST_ID, self.vocab, size=self._shared).tolist()
            for g in range(self._groups)] if self._shared else []

    def _fill(self) -> None:
        p_order = self._layout.permutation(self.count)
        o_order = self._layout.permutation(self.count)
        for i in range(self.count):
            n = self._prompt_lens[p_order[i]]
            ids = self._rng.integers(_FIRST_ID, self.vocab, size=n).tolist()
            if self._prefixes:
                pre = self._prefixes[self._uid % self._groups][:n - 1]
                ids[:len(pre)] = pre
            self._queue.append(Request(
                uid=self._uid, prompt_ids=ids,
                max_tokens=self._output_lens[o_order[i]],
                sampling_seed=int(self._rng.integers(1, 2 ** 31 - 1)),
                due_s=None if self._due is None else self._due[i]))
            self._uid += 1

    def all(self) -> list:
        """The whole laid-out set, once (open loop)."""
        if not self._queue:
            self._fill()
        out, self._queue = self._queue, []
        return out

    def next(self) -> Request:
        if not self._queue:
            self._fill()
        return self._queue.pop(0)

    def random_ids(self, n: int) -> list:
        """n token ids off this run's stream (warm-up probes)."""
        return self._rng.integers(_FIRST_ID, self.vocab, size=n).tolist()

    def sizes(self) -> tuple:
        """The distinct prompt lengths and the distinct output lengths,
        for the warm-up."""
        return sorted(set(self._prompt_lens)), sorted(set(self._output_lens))
