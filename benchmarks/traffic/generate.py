"""The one general generator: reads a traffic mix (a data file of parameters)
and makes, from ``--seed``, what the program is fed.

Every seed gives the same sizes (training: the same shapes; serving: the same
set of length pairs a cycle) with other token values, and for serving in
another order: the amount of work in a cycle does not depend on the seed.

``percentile``, ``poisson_arrivals`` and the per-token tap's arithmetic are
copies of the sound parts of ``serve/loadgen.py`` (see PERF.md, Open
questions).
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist

import numpy as np


# ---- training --------------------------------------------------------------
def train_dataset(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """``[sequences, seq_len]`` int32 token rows, all different: uniform
    tokens, each replaced with probability ``repeat_p`` by its predecessor so
    that there is something to learn and the loss falls."""
    rng = np.random.default_rng([int(seed), 0x7261696E])
    n, s = traffic["sequences"], traffic["seq_len"]
    base = rng.integers(0, vocab, size=(n, s), dtype=np.int64)
    repeat = rng.random((n, s)) < traffic.get("repeat_p", 0.5)
    repeat[:, 0] = False
    # a run of repeats copies the last fresh token
    idx = np.where(repeat, 0, np.arange(s)[None, :])
    idx = np.maximum.accumulate(idx, axis=1)
    return np.take_along_axis(base, idx, axis=1).astype(np.int32)


# ---- serving ---------------------------------------------------------------
def lengths(spec: dict, n: int) -> list:
    """``n`` lengths: all ``fixed``, or the evenly spaced quantiles of a
    log-normal with the given ``median`` and ``sigma``, clipped to [``min``,
    ``max``]. A fixed set either way."""
    if "fixed" in spec:
        return [int(spec["fixed"])] * n
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def length_pairs(traffic: dict, seed: int, cycle: int) -> list:
    """One cycle of (prompt_len, output_len) pairs: the mix's own set, paired
    the same way for every seed, in an order drawn from the seed."""
    n = traffic["distinct_requests"]
    prompts = lengths(traffic["prompt_len"], n)
    outputs = lengths(traffic["output_len"], n)
    random.Random("pairing").shuffle(outputs)
    pairs = list(zip(prompts, outputs))
    random.Random(f"{int(seed)}:order:{cycle}").shuffle(pairs)
    return pairs


class RequestStream:
    """An endless stream of (prompt_ids, output_len), cycle after cycle of the
    mix's set of lengths. Prompts are uniform tokens drawn from the seed and
    share nothing.

    ``"first_output_len": "staggered"`` starts a closed loop out of lock-step:
    the first reply of client ``i`` of ``n`` (the stream's first ``n``
    requests) is ``(i + 1) / n`` of its drawn length, every later reply whole.
    Clients that start together on equal sizes would otherwise end together,
    round after round; with it one reply ends every ``length / n`` decode
    steps, the same for every seed (it fixes a phase, not an order)."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.traffic, self.vocab, self.seed = traffic, vocab, int(seed)
        first = traffic.get("first_output_len", "whole")
        if first not in ("whole", "staggered"):
            raise ValueError(f"first_output_len {first!r}: whole or staggered")
        self.stagger_over = traffic["clients"] if first == "staggered" else 0
        self.rng = np.random.default_rng([self.seed, 0x73727665])
        self.cycle, self.pending = 0, []
        self.issued = 0

    def __iter__(self):
        return self

    def __next__(self):
        if not self.pending:
            self.pending = length_pairs(self.traffic, self.seed, self.cycle)
            self.cycle += 1
        n_prompt, n_out = self.pending.pop(0)
        self.issued += 1
        if self.issued <= self.stagger_over:
            n_out = max(1, n_out * self.issued // self.stagger_over)
        return self.rng.integers(0, self.vocab, size=n_prompt).tolist(), n_out


def poisson_arrivals(rate_rps: float, duration_s: float, seed: int) -> list:
    """Arrival offsets of a Poisson process over ``duration_s`` seconds."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    rng = random.Random(f"{int(seed)}:arrivals")
    t, out = 0.0, []
    while True:
        t += rng.expovariate(rate_rps)
        if t >= duration_s:
            return out
        out.append(t)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in [0, 1]: never a value not measured."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, int(round(q * (len(ordered) - 1)))))
    return float(ordered[idx])


class TokenTap:
    """Per-token arrival stamps: one timestamp per output token of each
    request, stamped the iteration its stream first shows it."""

    def __init__(self):
        self.times: dict[int, list] = {}

    def stamp(self, request_id: int, n_tokens: int, now: float):
        times = self.times.setdefault(request_id, [])
        new = n_tokens - len(times)
        if new > 0:
            times.extend([now] * new)

    def gaps(self, t_from: float, t_to: float) -> list:
        """Gaps between consecutive tokens of one request, for tokens that
        arrived inside [t_from, t_to]."""
        out = []
        for times in self.times.values():
            out.extend(b - a for a, b in zip(times, times[1:])
                       if a >= t_from and b <= t_to)
        return out
