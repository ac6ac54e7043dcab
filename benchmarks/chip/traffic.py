"""The one traffic generator: a mix is a data file (traffic/<name>.json)
of parameters, and this module turns it and a seed into a schedule.

Three shapes of traffic, named by the file's `loop`:

  closed     `clients` callers, each sending its next request when the
             last one completes (batch generation, agents).  Lengths are
             drawn per block of `clients` requests; the requests a
             long-running loop would have in service when the window
             opens carry the stationary residual of the answer length.
  sessions   open loop: sessions start at `rate_per_s` on a schedule
             (gaps exponential, or gamma with a coefficient of variation),
             whatever the server does; each is a chat of several turns
             whose prompt grows by the served answer and a new user
             message, with a think time between turns.
  launches   closed loop over a list of kernels, one launch after the
             other, with data drawn per launch.

Sizes are stratified: a set of n draws takes the distribution's
quantiles at (i + 0.5) / n in an order shuffled by the seed, so every
seed offers the same multiset of sizes and gaps, in another order, and
only the order and the token contents change with the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

FIRST_TOKEN_ID = 2          # ids 0 and 1 stay free (padding, eos)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def _ppf(dist: Dict[str, Any], u: float) -> float:
    """Inverse CDF of one of the mixes' length / gap distributions."""
    kind = dist["dist"]
    if kind == "uniform_int":           # integers lo..hi, both included
        lo, hi = dist["lo"], dist["hi"]
        return float(min(hi, lo + math.floor(u * (hi - lo + 1))))
    if kind == "lognormal":             # median, sigma of log
        return dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(u))
    if kind == "exponential":
        return -dist["mean"] * math.log(1.0 - u)
    if kind == "gamma":                 # mean, coefficient of variation
        from scipy.stats import gamma as _gamma   # noqa: PLC0415
        k = 1.0 / dist["cv"] ** 2
        return float(_gamma.ppf(u, k, scale=dist["mean"] / k))
    raise ValueError(f"unknown distribution {kind!r}")


def quantiles(dist: Dict[str, Any], n: int, integer: bool = False
              ) -> List[float]:
    """The distribution's quantiles at (i + 0.5) / n, in order, clipped to
    its `min` / `cap` where it has them."""
    vals = [_ppf(dist, (i + 0.5) / n) for i in range(n)]
    if "min" in dist:
        vals = [max(v, dist["min"]) for v in vals]
    if "cap" in dist:
        vals = [min(v, dist["cap"]) for v in vals]
    if integer:
        vals = [int(round(v)) for v in vals]
    return vals


def stratified(dist: Dict[str, Any], n: int, rng: np.random.Generator,
               integer: bool = False) -> List[float]:
    """n draws: `quantiles`, shuffled."""
    vals = quantiles(dist, n, integer)
    return [vals[i] for i in rng.permutation(n)]


def residual(dist: Dict[str, Any], n: int, rng: np.random.Generator
             ) -> List[int]:
    """n draws, stratified and shuffled, of what is left of a length drawn
    from `dist` in a request found in service at a random moment of a
    loop that has run for long: P(r) is proportional to P(L >= r),
    r >= 1 (the residual life of a renewal process)."""
    lengths = np.asarray(quantiles(dist, 4096, integer=True))
    r = np.arange(1, lengths.max() + 1)
    survive = (lengths[None, :] >= r[:, None]).sum(1)
    cdf = np.cumsum(survive) / survive.sum()
    vals = [int(r[np.searchsorted(cdf, (i + 0.5) / n)]) for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(FIRST_TOKEN_ID, vocab, n).tolist()


# ----------------------------------------------------------------- closed

def closed_requests(mix: Dict[str, Any], seed: int, n_requests: int,
                    vocab: int, max_len: int, in_service: int = 0
                    ) -> List[Dict[str, Any]]:
    """The first `n_requests` requests of a closed-loop mix, in the order
    the clients send them.  The first `in_service` stand for the requests
    a long-running loop has in its slots: their answers are the stationary
    residual of `max_new` (`residual`).  After them, each block of
    `clients` requests holds the same stratified lengths."""
    block = mix["clients"]
    size_rng, tok_rng = _rng(seed, 1), _rng(seed, 2)
    sizes = []
    if in_service:
        sizes = list(zip(
            stratified(mix["prompt_len"], in_service, size_rng, integer=True),
            residual(mix["max_new"], in_service, size_rng)))
    while len(sizes) < n_requests:
        sizes += zip(stratified(mix["prompt_len"], block, size_rng, True),
                     stratified(mix["max_new"], block, size_rng, True))
    out: List[Dict[str, Any]] = []
    for p, m in sizes[:n_requests]:
        if p + m > max_len - 1:
            raise ValueError(f"prompt {p} + max_new {m} does not fit "
                             f"max_len {max_len}")
        out.append({"prompt": _tokens(tok_rng, p, vocab), "max_new": m})
    return out


# --------------------------------------------------------------- sessions

def sessions(mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
             max_len: int) -> Dict[str, Any]:
    """An open-loop schedule of chat sessions starting in [0, seconds).

    Returns {"system": [tokens], "sessions": [{"start_s", "turns": [
    {"user": [tokens], "max_new", "think_s"}]}]}.  Turn k's prompt is
    system + (user_1 + answer_1) + ... + user_k, where answer_j is the
    max_new_j + 1 tokens the server returns (its prefill token and
    max_new_j decoded ones); user messages are cut so that every prompt
    plus its max_new stays under max_len - 1, and a session ends early
    where no `user_len.min` tokens fit."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = _rng(seed, 3)
    gaps = stratified(dict(mix["arrivals"], mean=1.0 / mix["rate_per_s"]),
                      n, rng)
    # session i starts after gaps 0..i: the last start is the same sum
    # for every seed
    starts = np.cumsum(gaps).tolist()
    n_turns = stratified(mix["turns"], n, rng, integer=True)
    total = sum(n_turns)
    users = stratified(mix["user_len"], total, rng, integer=True)
    answers = stratified(mix["answer_len"], total, rng, integer=True)
    thinks = stratified(mix["think_s"], total, rng)
    tok_rng = _rng(seed, 4)
    system = _tokens(tok_rng, mix["system_prompt_len"], vocab)
    min_user = mix["user_len"].get("min", 1)
    out, j = [], 0
    for i in range(n):
        used = len(system)
        turns = []
        for _ in range(n_turns[i]):
            m, u = answers[j], users[j]
            room = max_len - 1 - m - used
            if room < min_user:
                j += 1
                break
            u = min(u, room)
            turns.append({"user": _tokens(tok_rng, u, vocab), "max_new": m,
                          "think_s": thinks[j]})
            used += u + m + 1
            j += 1
        out.append({"start_s": starts[i], "turns": turns})
    return {"system": system, "sessions": out}


# --------------------------------------------------------------- launches

def launches(mix: Dict[str, Any], seed: int, n_passes: int
             ) -> List[Dict[str, Any]]:
    """`n_passes` passes over the mix's kernels, in the listed order, each
    launch with a data seed of its own drawn from `seed`."""
    rng = _rng(seed, 5)
    out = []
    for _ in range(n_passes):
        for k in mix["kernels"]:
            out.append({"name": k["name"], "kwargs": dict(k["kwargs"]),
                        "miss_latency": k["miss_latency"],
                        "data_seed": int(rng.integers(0, 2**31 - 1))})
    return out
