"""One generator for every traffic mix; a mix is a data file of parameters.

A mix file (``mixes/<name>.json``) holds:

  loop       "closed": ``clients`` callers, each sending its next request
             when the last is answered; "open": requests due on a schedule
             at ``rate_per_s``, sent by ``senders`` threads over
             ``clients`` connections, each timed from when it was due.
  rows       {"min", "max"}: rows per request, log-uniform integers.
  draw       "uniform" over the catalog rows, or "zipf" with exponent
             ``zipf_s`` over a seeded ranking of them.
  perturb    relative noise: each feature of a fresh row is multiplied by
             ``1 + perturb * N(0, 1)``, so no two fresh rows repeat.
  fresh      share of the rows that are fresh (default 1); the others are
             catalog rows as they are, which the engine's cache can answer.

The work does not depend on the seed: a closed-loop client cycles through
a fixed set of sizes in a fixed, spread-out order (``closed_sizes``),
and an open loop's arrival gaps are a fixed set that the seed only
reorders. The rows drawn and their noise follow the seed. So two seeds do
the same work on different rows, even in a window that holds few
requests.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

MIXES = Path(__file__).resolve().parent / "mixes"
GRID = 64            # sizes in one cycle of a closed-loop client


def load_mix(name: str) -> dict:
    return json.loads((MIXES / f"{name}.json").read_text())


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent stream of ``seed``: (0,) forest, (1, ...) traffic,
    (2,) the sample that is checked."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, *stream])


def size_grid(mix: dict) -> np.ndarray:
    """The fixed set of request sizes: log-uniform quantiles."""
    lo, hi = mix["rows"]["min"], mix["rows"]["max"]
    if lo == hi:
        return np.full(GRID, lo, np.int64)
    q = (np.arange(GRID) + 0.5) / GRID
    return np.rint(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
                   ).astype(np.int64)


def dispatch_sizes(mix: dict, dispatch_batch: int) -> list[int]:
    """Every power-of-two batch the frontend can hand the engine under this
    mix: merges of up to ``dispatch_batch`` queued requests (``clients`` of
    them in a closed loop)."""
    most = mix["rows"]["max"] * (mix["clients"] if mix["loop"] == "closed"
                                 else dispatch_batch)
    lo = (mix["rows"]["min"] - 1).bit_length()
    return [1 << k for k in range(lo, (most - 1).bit_length() + 1)]


@dataclass
class Rows:
    """Feature rows drawn from the catalog: ``idx`` says which catalog row
    each one is, ``fresh`` which of them were perturbed."""
    X: np.ndarray        # (n, F) float32
    idx: np.ndarray      # (n,) int64
    fresh: np.ndarray    # (n,) bool

    def __getitem__(self, sl) -> "Rows":
        return Rows(self.X[sl], self.idx[sl], self.fresh[sl])


class Drawer:
    def __init__(self, mix: dict, catalog: np.ndarray, seed: int):
        self.mix = mix
        self.catalog = np.ascontiguousarray(catalog, np.float32)
        n = len(catalog)
        if mix["draw"] == "zipf":
            rank = rng_for(seed, 1, 0).permutation(n)
            w = 1.0 / np.arange(1, n + 1) ** mix["zipf_s"]
            self.p = np.empty(n)
            self.p[rank] = w / w.sum()
        elif mix["draw"] == "uniform":
            self.p = None
        else:
            raise ValueError(f"unknown draw {mix['draw']!r}")

    def rows(self, n: int, rng: np.random.Generator) -> Rows:
        """``n`` rows, of which ``round(fresh * n)`` fresh ones."""
        idx = rng.choice(len(self.catalog), size=n, p=self.p)
        X = self.catalog[idx]
        fresh = np.zeros(n, bool)
        fresh[rng.permutation(n)[:round(self.mix.get("fresh", 1.0) * n)]] = 1
        noise = rng.standard_normal((int(fresh.sum()), X.shape[1]))
        X[fresh] = X[fresh] * (1.0 + self.mix["perturb"] * noise)
        return Rows(X, idx, fresh)


def closed_sizes(mix: dict, client: int) -> np.ndarray:
    """Client ``client``'s sizes, cycled through by the caller: the grid in
    bit-reversed order, so that any run of them spans the range, shifted
    by ``client`` shares of the range, so that the clients' requests in
    flight at one time are small to large, not four alike (merged into one
    call, requests return together and their clients stay in step)."""
    grid = np.sort(size_grid(mix))
    bits = (GRID - 1).bit_length()
    rev = np.array([int(f"{i:0{bits}b}"[::-1], 2) for i in range(GRID)])
    return grid[(rev + client * GRID // mix["clients"]) % GRID]


def open_schedule(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of ``rate_per_s * seconds``
    requests: exponential-quantile gaps, shuffled, scaled to end at
    ``seconds``."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng_for(seed, 1, 2).permutation(gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())
