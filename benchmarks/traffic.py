"""The one general traffic generator. A traffic mix is a data file under
``benchmarks/traffic/`` (lengths, sharing, arrivals); this module turns it
and a seed into token batches for a training cell or requests for a
serving cell. The serving half follows ``paddle_tpu/serve/loadgen.py``
(log-normal ragged lengths, sessions with shared prefixes, Poisson or
bursty arrivals) with one change made for steadiness: every seed gets the
SAME multiset of lengths (the stratified quantiles of the log-normal) in
another order with other token ids, so that two seeds give the system the
same amount of work.
"""

from __future__ import annotations

import json
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _rng(seed: int, stream: int) -> np.random.RandomState:
    return np.random.RandomState([int(seed) % 2 ** 32, stream])


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_batches(mix: Dict[str, Any], vocab: int, batch: int,
                  seed: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """An endless stream of next-token batches ``(x, y)``, ``[batch,
    seq_len]`` int32 each, every row a fresh draw: unpacked sequences of
    ``seq_len + 1`` token ids from a Zipf unigram over the whole
    vocabulary, ranks mapped to ids by a seeded permutation."""
    T = int(mix["seq_len"])
    spec = mix["tokens"]
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) \
        ** float(spec["exponent"])
    cdf = np.cumsum(p / p.sum())
    ids = _rng(seed, 0).permutation(vocab).astype(np.int32)
    rng = _rng(seed, 1)
    while True:
        ranks = np.searchsorted(cdf, rng.random_sample((batch, T + 1)))
        toks = ids[np.minimum(ranks, vocab - 1)]
        yield toks[:, :-1], toks[:, 1:]


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _stratified_lengths(lo: int, hi: int, sigma: float, n: int) -> List[int]:
    """The n stratified quantiles of a log-normal around the geometric
    mean of [lo, hi], clipped to it."""
    lo, hi = max(1, int(lo)), int(hi)
    mu = math.log(math.sqrt(lo * hi))
    nd = NormalDist()
    return [int(np.clip(round(math.exp(mu + sigma * nd.inv_cdf(
        (i + 0.5) / n))), lo, hi)) for i in range(n)]


def _balanced_order(sizes: List[int], group: int,
                    rng: np.random.RandomState) -> List[int]:
    """``sizes`` (sorted) in a seeded order in which every aligned run of
    ``group`` consecutive entries holds one size from each ``group``-quantile
    of the list, so that whichever ``group`` requests are in flight carry
    about the same work. ``group`` 1 is a plain permutation."""
    n = len(sizes)
    if group <= 1 or n % group:
        return [sizes[i] for i in rng.permutation(n)]
    per = n // group                 # sizes a quantile, and runs in all
    runs: List[List[int]] = [[] for _ in range(per)]
    for q in range(group):
        members = sizes[q * per:(q + 1) * per]
        for run, i in zip(runs, rng.permutation(per)):
            run.append(members[i])
    return [run[i] for run in runs for i in rng.permutation(group)]


def serve_requests(mix: Dict[str, Any], vocab: int,
                   seed: int) -> List[Dict[str, Any]]:
    """``mix["pool"]`` requests, each ``{"prompt", "max_new", "at_s",
    "session"}``. Clients (closed loop) or the arrival clock (open loop)
    take them in order and start again from the first when the pool is
    used up. ``at_s`` is the arrival offset of an open loop (Poisson, or
    a two-state bursty Poisson) and 0.0 in a closed one. ``mix["balance"]`` (a
    divisor of the pool, as a rule the number of slots) spreads the long
    and the short evenly over the order, see :func:`_balanced_order`."""
    n = int(mix["pool"])
    sigma = float(mix.get("sigma", 0.6))
    rng = _rng(seed, 2)
    plens = _stratified_lengths(*mix["prompt_len"], sigma, n)
    news = _stratified_lengths(*mix["max_new"], sigma, n)
    group = int(mix.get("balance", 1))
    plens = _balanced_order(plens, group, rng)
    news = _balanced_order(news, group, rng)
    n_sessions = int(mix.get("n_sessions", 0))
    prefix_len = int(mix.get("session_prefix_len", 0))
    prefixes = [list(map(int, rng.randint(1, vocab, prefix_len)))
                for _ in range(n_sessions)]
    in_session = set()
    if n_sessions:
        k = int(round(float(mix.get("p_session", 0.0)) * n))
        in_session = set(map(int, rng.permutation(n)[:k]))
    max_total = int(mix["max_total"])
    rate = float(mix.get("rate_rps", 0.0))
    bursty = mix.get("arrival", "poisson") == "bursty"
    t, in_burst, out = 0.0, False, []
    for i in range(n):
        if rate > 0:
            r = rate * (float(mix.get("burst_factor", 6.0))
                        if in_burst else 1.0)
            t += float(rng.exponential(1.0 / r))
            if bursty and rng.rand() < 1.0 / float(mix.get("burst_len", 4)):
                in_burst = not in_burst
        sid = None
        if i in in_session:
            sid = int(rng.randint(n_sessions))
            tail = max(1, plens[i] - prefix_len)
            prompt = prefixes[sid] + list(map(int, rng.randint(
                1, vocab, tail)))
        else:
            prompt = list(map(int, rng.randint(1, vocab, plens[i])))
        prompt = prompt[:max_total - 1]
        out.append({"prompt": prompt, "at_s": round(t, 6), "session": sid,
                    "max_new": max(1, min(news[i],
                                          max_total - len(prompt)))})
    return out


def first_budget_shares(n_clients: int, seed: int) -> List[float]:
    """The share of its budget each client's FIRST request keeps, so that
    the window opens with the clients out of step: the stratified points
    of (0, 1], in a seeded order."""
    order = _rng(seed, 3).permutation(n_clients)
    return [float((k + 1) / n_clients) for k in order]
