"""One general generator of serving traffic; a mix is a file of parameters.

``schedule(mix, seed, seconds, vocab_size)`` turns ``benchmarks/traffic/<mix>.json``
into the list of requests of one run. Every seed gives the same multiset of
prompt lengths, output lengths and gaps between arrivals (a quantile grid of
each distribution) in another order and with other tokens, so two seeds offer
the same load and differ only in what meets what.

Parameters of a mix (all optional but ``arrival`` and the two lengths):

  arrival      {"process": "poisson" | "gamma" | "closed",
                "rate_per_s": r,           open loop: mean arrivals a second
                "cv": c,                   gamma: coefficient of variation of the gaps
                "clients": n}              closed loop: streams that each wait for a reply
  preroll_s    arrivals start this long before the window so that it opens on
               a loaded system; requests due before it are set-up, not samples
  prompt_len,  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  output_len   {"dist": "uniform", "min": a, "max": b}
               {"dist": "mixture", "values": [...], "weights": [...]}
  stratified   true (default): quantile grid shuffled by the seed; false: plain draws
  schedule_seed  if given, the run is a fixed-trace replay: arrival times, lengths
               and which request is sampled come from this number and are the same in
               every run, and --seed changes only the tokens, the sampling seeds and
               the weights. A metric of such a cell says how the system serves that
               one schedule, not the mix at large.
  shared_prefix {"groups": g, "prefix_len": n, "share": s}  a share of requests
               starts with one of g fixed prefixes of n tokens
  sessions     {"turns": t, "think_time_s": s, "growth": <length dist>}  a request
               opens a session; each later turn resends the whole history plus growth
  sampling     {"sampled_share": s, "temperature": t, "top_k": k}  the rest is greedy
  grace_s      after the window, how long a request may still wait for its first token
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "mixture":
        total, acc = sum(dist["weights"]), 0.0
        x = dist["values"][-1]
        for value, weight in zip(dist["values"], dist["weights"]):
            acc += weight / total
            if u <= acc:
                x = value
                break
        return x
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(x, dist["min"]), dist["max"])


def mean_length(dist: dict, grid: int = 400) -> float:
    """Mean of a length distribution, over its quantile grid."""
    return sum(_quantile(dist, (i + 0.5) / grid) for i in range(grid)) / grid


def draws(dist: dict, n: int, rng: random.Random, stratified: bool) -> list[int]:
    """n whole-number draws: a shuffled quantile grid, or independent ones."""
    if stratified:
        us = [(i + 0.5) / n for i in range(n)]
        rng.shuffle(us)
    else:
        us = [rng.random() for _ in range(n)]
    return [int(round(_quantile(dist, u))) for u in us]


def _gamma_quantile(shape: float, u: float) -> float:
    """Quantile of Gamma(shape, 1) by bisection on the regularised lower
    incomplete gamma function (series; fine for the shapes a CV of 0.3-5 gives)."""

    def cdf(x):
        if x <= 0:
            return 0.0
        term = total = 1.0 / shape
        for k in range(1, 400):
            term *= x / (shape + k)
            total += term
            if term < 1e-14 * total:
                break
        return min(1.0, total * math.exp(-x + shape * math.log(x) - math.lgamma(shape)))

    lo, hi = 0.0, max(10.0, shape * 20.0)
    for _ in range(80):
        mid = (lo + hi) / 2
        if cdf(mid) < u:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def arrival_times(arrival: dict, span_s: float, rng: random.Random, stratified: bool) -> list[float]:
    """Open loop: times in [0, span_s) of round(rate x span) arrivals."""
    n = max(1, int(round(arrival["rate_per_s"] * span_s)))
    if arrival["process"] == "poisson":
        shape = 1.0
    elif arrival["process"] == "gamma":
        shape = 1.0 / float(arrival["cv"]) ** 2
    else:
        raise ValueError(f"no arrival times for process {arrival['process']!r}")
    if stratified:
        us = [(i + 0.5) / n for i in range(n)]
        rng.shuffle(us)
    else:
        us = [rng.random() for _ in range(n)]
    if shape == 1.0:
        gaps = [-math.log(1.0 - u) for u in us]
    else:
        gaps = [_gamma_quantile(shape, u) for u in us]
    # The same n arrivals fill the same span for every seed: the gaps are
    # scaled so that the n-th ends one mean gap before the end.
    scale = span_s * n / (n + 1) / sum(gaps)
    times, t = [], 0.0
    for g in gaps:
        t += g * scale
        times.append(t)
    return times


def schedule(mix: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    """The requests of one run. Returns ``{"open": [...]}`` (each with a ``due_s``
    relative to the start of the window, negative inside the pre-roll) or
    ``{"closed": [[...] per client]}``; a request is
    ``{"tokens", "max_new_tokens", "temperature", "top_k", "seed", "due_s"}`` and, in a
    mix with sessions, ``"followups"``."""
    rng = random.Random(int(seed))
    order = random.Random(int(mix["schedule_seed"])) if "schedule_seed" in mix else rng
    stratified = bool(mix.get("stratified", True))
    arrival = mix["arrival"]
    preroll = float(mix.get("preroll_s", 0.0))
    sessions = mix.get("sessions")
    turns = int(sessions["turns"]) if sessions else 1

    if arrival["process"] == "closed":
        clients = int(arrival["clients"])
        per_client = int(arrival.get("requests_per_client", 8))
        n = clients * per_client
        dues = [None] * n
    else:
        dues = [t - preroll for t in arrival_times(arrival, preroll + seconds, order, stratified)]
        n = len(dues)

    prompt_lens = draws(mix["prompt_len"], n, order, stratified)
    output_lens = [draws(mix["output_len"], n, order, stratified) for _ in range(turns)]
    growth = [draws(sessions["growth"], n, order, stratified) for _ in range(turns - 1)] if sessions else []

    prefix = mix.get("shared_prefix")
    prefixes = []
    if prefix:
        prefixes = [
            [rng.randrange(vocab_size) for _ in range(int(prefix["prefix_len"]))]
            for _ in range(int(prefix["groups"]))
        ]
    sampling = mix.get("sampling", {})
    n_sampled = int(round(float(sampling.get("sampled_share", 0.0)) * n))
    sampled = [i < n_sampled for i in range(n)]
    order.shuffle(sampled)

    requests = []
    for i in range(n):
        tokens = []
        if prefix and rng.random() < float(prefix.get("share", 1.0)):
            tokens = list(prefixes[rng.randrange(len(prefixes))])
        body = max(1, prompt_lens[i] - len(tokens))
        tokens += [rng.randrange(vocab_size) for _ in range(body)]
        req = {
            "tokens": tokens,
            "max_new_tokens": output_lens[0][i],
            "temperature": float(sampling.get("temperature", 0.0)) if sampled[i] else 0.0,
            "top_k": int(sampling.get("top_k", 0)) if sampled[i] else 0,
            "seed": rng.randrange(2**31),
            "due_s": dues[i],
        }
        if sessions:
            # Later turns: the client resends its history (prompt and reply)
            # plus this many new tokens, after thinking.
            req["followups"] = [
                {
                    "new_tokens": [rng.randrange(vocab_size) for _ in range(growth[t][i])],
                    "max_new_tokens": output_lens[t + 1][i],
                    "think_time_s": float(sessions["think_time_s"]),
                }
                for t in range(turns - 1)
            ]
        requests.append(req)

    if arrival["process"] == "closed":
        clients = int(arrival["clients"])
        return {"closed": [requests[c::clients] for c in range(clients)]}
    return {"open": requests}


def offered_tokens(plan: dict) -> tuple[int, int]:
    """(prompt tokens, output tokens) of a plan's first turns: what two seeds
    must agree on."""
    reqs = plan["open"] if "open" in plan else [r for c in plan["closed"] for r in c]
    return sum(len(r["tokens"]) for r in reqs), sum(r["max_new_tokens"] for r in reqs)
