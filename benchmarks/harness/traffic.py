"""The one general traffic generator.  A traffic mix is a data file under
``benchmarks/traffic/``; this module turns it and a seed into inputs.  The
same seed gives the same inputs; the program under test only ever sees what
is generated here.

A mix file has a ``kind``:

``train``
    ``{"kind": "train", "batch": 2, "seq_len": 8192,
       "tokens": {"dist": "uniform"}, "skip_steps": 2}`` —
    ``train_batch(mix, vocab, seed, step)`` gives the host batch of a step:
    seeded uniform token ids and next-token labels (the last label of a row
    is -1, ignored).  A fresh batch every step.

``serve``
    ``loop`` is ``"open"`` (arrivals on a schedule, whatever the server does)
    or ``"closed"`` (a backlog of ``backlog`` requests kept queued: a new one
    is submitted when one is admitted).  ``prompt_len`` / ``output_len`` are
    length distributions; ``arrivals`` (open loop) is the arrival process.

Length distributions: ``{"dist": "lognormal", "median": m, "sigma": s,
"min": lo, "max": hi}`` (clipped).  With ``"stratify": n`` the draws come in
blocks of ``n``: the ``n`` mid-quantiles of the distribution in ONE seeded
order, repeated block after block.  Any run of a few dozen requests then
has the distribution's mix of lengths whatever the seed — the amount of
work in a window is fixed and only its order is drawn — and the traffic is
periodic, so a closed loop settles into a cycle one block long, which
``serve_runner.served_rate`` times.

``"order_seed": n`` (a serve mix's own key) draws the lengths, and so the
order of every stratified block, from ``n`` and not from the run's seed: the
run's seed then draws the arrivals and the token ids only, and every run of
the mix serves the same requests in the same order.  On the v5e (PR 22) the
ORDER moves a saturated server's rate by several percent (1594-1750 tokens/s
over seeded orders and entry positions of ``docs-backlog``; the driver read
the middle half of six seeded orders 9-10% wide) while runs of one order
agree to 0.04-0.2%, so a saturated closed loop fixes its order: no bound a
benchmark may set holds a 10% spread.  A change to admission or scheduling
moves the order too; the mix that shows what an order is worth is another
data file with another ``order_seed``.

Arrival processes: ``{"process": "poisson", "rate_per_s": r}`` — exponential
gaps, as ``serving.driver.poisson_arrivals`` draws them (its arithmetic,
copied); with ``"fixed_count": true`` the schedule over ``horizon_s`` holds
exactly ``round(r * horizon_s)`` arrivals at sorted uniform times, which is
the same process conditioned on its count (so every seed offers the same
load).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution at probabilities ``u``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in u])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(x, dist["min"], dist["max"])


def draw_lengths(dist: dict, n: int, rs: np.random.RandomState) -> np.ndarray:
    """``n`` integer lengths from ``dist`` (stratified in blocks of one
    order drawn from ``rs`` when the distribution says so)."""
    block = int(dist.get("stratify", 0))
    if block <= 0:
        return np.rint(_quantile(dist, rs.uniform(size=n))).astype(np.int64)
    mids = _quantile(dist, (np.arange(block) + 0.5) / block)
    order = rs.permutation(block)
    return np.rint(np.tile(mids[order], -(-n // block))[:n]).astype(np.int64)


def arrival_times(arrivals: dict, horizon_s: float,
                  rs: np.random.RandomState) -> np.ndarray:
    """Sorted due times in ``[0, horizon_s)`` (seconds from the schedule's
    start) of an open loop."""
    rate = float(arrivals["rate_per_s"])
    if arrivals.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    if rate <= 0 or not math.isfinite(rate):
        raise ValueError(f"arrival rate must be positive, got {rate}")
    if arrivals.get("fixed_count"):
        n = max(int(round(rate * horizon_s)), 1)
        return np.sort(rs.uniform(0.0, horizon_s, size=n))
    gaps = rs.exponential(1.0 / rate, size=int(rate * horizon_s * 1.5) + 16)
    t = np.cumsum(gaps) - gaps[0]  # the schedule starts with work
    return t[t < horizon_s]


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    rid: int
    due_s: float          # open loop: seconds from the schedule's start
    prompt: np.ndarray    # int32 token ids, distinct between requests
    max_new: int


def serve_requests(mix: dict, vocab: int, seed: int, horizon_s: float,
                   n_closed: int = 0) -> List[ServeRequest]:
    """The requests of one run.  Open loop: one per arrival in the horizon.
    Closed loop: ``n_closed`` of them, all due at 0 (the runner feeds them
    as the backlog drains)."""
    rs = np.random.RandomState(seed)
    if mix["loop"] == "open":
        due = arrival_times(mix["arrivals"], horizon_s, rs)
    elif mix["loop"] == "closed":
        due = np.zeros(n_closed)
    else:
        raise ValueError(f"loop must be 'open' or 'closed', got {mix['loop']!r}")
    n = len(due)
    # lengths from the mix's own order_seed where it has one: the run's seed
    # then moves the token ids (and the arrivals) and not the order
    ls = (np.random.RandomState(int(mix["order_seed"]))
          if "order_seed" in mix else rs)
    plen = draw_lengths(mix["prompt_len"], n, ls)
    olen = draw_lengths(mix["output_len"], n, ls)
    # token 0 is the pad id of left-padded rows: prompts draw from 1..V-1.
    # Uniform random ids make every prompt distinct from its first page on,
    # so a prefix cache finds nothing unless the mix shares on purpose.
    return [ServeRequest(rid=i, due_s=float(due[i]),
                         prompt=rs.randint(1, vocab, size=int(plen[i]),
                                           dtype=np.int64).astype(np.int32),
                         max_new=int(olen[i]))
            for i in range(n)]


def train_batch(mix: dict, vocab: int, seed: int, step: int) -> dict:
    """The host batch of training step ``step``: ``ids [B, S]`` seeded
    uniform token ids, ``labels`` the next token (last label of a row -1)."""
    if mix.get("tokens", {"dist": "uniform"})["dist"] != "uniform":
        raise ValueError("train mixes draw uniform token ids")
    rs = np.random.RandomState([seed & 0x7FFFFFFF, step])
    ids = rs.randint(0, vocab, size=(mix["batch"], mix["seq_len"]),
                     dtype=np.int64).astype(np.int32)
    labels = np.concatenate(
        [ids[:, 1:], np.full((ids.shape[0], 1), -1, np.int32)], axis=1)
    return {"ids": ids, "labels": labels}
