"""The one traffic generator: a traffic file's parameters and a seed in,
a fixed list of requests (or a stream of training batches) out.

A traffic mix is DATA (`benchmarks/traffic/<name>.json`): lengths, rates,
burstiness, the mixture of request classes, and the shape parameters the
engine or the step is given.  Nothing here knows a cell by name, so a later
cell is a new file: a PR that is not a benchmark PR may add data files and
nothing else, which is why the generator already draws mixtures of classes
and bursts although the first two mixes have one class and Poisson arrivals.

A serving mix fixes its SCHEDULE — when requests arrive and how long they
are — with `schedule_seed`; the run's `--seed` makes the contents (every
token id, and the weights).  A window holds on the order of a hundred
requests, and a 90th or 99th percentile read from a fresh draw of a hundred
moves by 7-22 % from draw to draw (measured on the chip in PR 22, PERF.md),
more than any bound a cell may have.  With the schedule fixed a run is a
replay, and its tails repeat as closely as the system's own timing does;
they are the tails of that ONE schedule.

Every schedule offers the same amount of work, so that runs differ by the
system and not by the draw:

- arrivals: the window holds exactly round(rate x seconds) requests.  The
  gaps are gamma-distributed with coefficient of variation `cv` (1 = a
  Poisson process, > 1 = bursts, as BurstGPT models them) and are scaled so
  that they fill the window — for cv 1 that IS a Poisson process given its
  count.
- lengths: stratified — one draw from each of n equal slices of the
  distribution, shuffled — so the total tokens barely move with the seed
  while every request still follows the stated distribution.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float              # seconds after the window opens
    prompt: np.ndarray        # int32 token ids
    max_new: int
    klass: int                # index into the traffic file's `classes`


# -- distributions -----------------------------------------------------------

def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution at u in (0, 1), as integers
    clipped to the distribution's [min, max]."""
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        x = lo + u * (hi + 1 - lo)         # integers lo..hi equally likely
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(v)) for v in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.floor(x)
    if "min" in dist:
        x = np.maximum(x, int(dist["min"]))
    if "max" in dist:
        x = np.minimum(x, int(dist["max"]))
    return x.astype(np.int64)


def stratified(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    """n integers following `dist`, one from each of n equal-probability
    slices, in random order."""
    u = (np.arange(n) + rng.uniform(0.0, 1.0, n)) / max(n, 1)
    return rng.permutation(quantile(dist, np.clip(u, 1e-9, 1 - 1e-9)))


def arrival_times(rng: np.random.Generator, arrivals: dict,
                  seconds: float) -> np.ndarray:
    """Due times in [0, seconds): exactly round(rate x seconds) of them."""
    n = int(round(float(arrivals["rate_per_s"]) * seconds))
    if n <= 0:
        return np.zeros(0)
    cv = float(arrivals.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0 / shape, n + 1)
    return np.cumsum(gaps)[:n] / np.sum(gaps) * seconds


# -- serving -----------------------------------------------------------------

def serve_requests(traffic: dict, vocab: int, seed: int,
                   seconds: float) -> list[Request]:
    """The requests of one run, in due order.  `classes` is a weighted
    mixture of (prompt length, output length) distributions."""
    schedule = np.random.default_rng([int(traffic["schedule_seed"]), 0x5E12])
    content = np.random.default_rng([int(seed), 0x70C5])
    due = arrival_times(schedule, traffic["arrivals"], seconds)
    n = len(due)
    classes = traffic["classes"]
    weights = np.array([float(c.get("weight", 1.0)) for c in classes])
    # the mixture is stratified too: class counts are fixed, order is not
    counts = np.floor(weights / weights.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1
    klass = schedule.permutation(np.repeat(np.arange(len(classes)), counts))
    t_p = np.zeros(n, np.int64)
    t_o = np.zeros(n, np.int64)
    for i, c in enumerate(classes):
        idx = np.flatnonzero(klass == i)
        t_p[idx] = stratified(schedule, c["prompt_len"], len(idx))
        t_o[idx] = stratified(schedule, c["output_len"], len(idx))
    return [Request(float(due[i]),
                    content.integers(0, vocab, int(t_p[i]), dtype=np.int32),
                    int(t_o[i]), int(klass[i])) for i in range(n)]


def _bucket(length: int, quantum: int) -> int:
    return -(-max(1, int(length)) // quantum) * quantum


def prefill_buckets(traffic: dict) -> list[int]:
    """Every prefill bucket the mix can reach: the engine pads a prompt up
    to a multiple of `prefill_quantum` and compiles one program per padded
    length, so these are the shapes to warm."""
    eng = traffic["engine"]
    q = int(eng.get("prefill_quantum") or eng["page_size"])
    buckets = set()
    for c in traffic["classes"]:
        d = c["prompt_len"]
        lo, hi = d.get("min"), d.get("max")
        if lo is None or hi is None:
            raise ValueError(
                "a prompt length distribution needs min and max: the "
                "warm-up must know every prefill bucket it can reach")
        buckets.update(range(_bucket(lo, q), _bucket(hi, q) + 1, q))
    return sorted(buckets)


def longest_stream(traffic: dict) -> int:
    """Most KV positions one request of the mix can need."""
    q = int(traffic["engine"].get("prefill_quantum")
            or traffic["engine"]["page_size"])
    longest = 0
    for c in traffic["classes"]:
        p, o = c["prompt_len"], c["output_len"]
        t_p = int(p["max"])
        longest = max(longest, _bucket(t_p, q), t_p + int(o["max"]))
    return longest


# -- training ----------------------------------------------------------------

def train_batch_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), 0x7EA1])
