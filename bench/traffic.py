"""The general traffic generator: one mix file in ``traffic/<mix>.json``
decides the arrivals and the choice of requests; this code reads any of
them.

A mix holds:

- ``loop``: ``"open"`` (independent users, requests due on a schedule
  whether or not earlier ones have finished) or ``"closed"`` (one client
  keeping a backlog: the next batch is taken as soon as the last is
  answered);
- ``rate_per_s`` (open loop): the mean offered rate;
- ``max_batch``: the most requests one service cycle takes;
- ``payload``: ``"frames"`` (camera frames through every stage) or
  ``"templates"`` (probe templates straight into the watchlist stage);
- ``tenant_shares`` (optional): each tenant's share of the requests;
- ``check_sample``: how many answered requests the correctness check
  compares with the reference.

Every seed gets the same amount of work in another order: the open
loop's gaps are the quantiles of the exponential distribution, shuffled,
so each seed has the same number of arrivals over the same span, and the
counts per tenant and of mated requests are fixed shares of the total.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def _counts(shares, n: int) -> np.ndarray:
    """``n`` split by ``shares`` with largest-remainder rounding."""
    w = np.asarray(shares, np.float64)
    exact = w / w.sum() * n
    out = np.floor(exact).astype(np.int64)
    short = n - int(out.sum())
    out[np.argsort(-(exact - out), kind="stable")[:short]] += 1
    return out


def open_schedule(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of the requests of one
    window of ``seconds``, sorted."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= seconds / gaps.sum()                   # exactly ``seconds``
    _rng(seed, 1).shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def choose(mix: dict, n: int, seed: int, tenants, mate_share: float,
           stream: int = 0):
    """What ``n`` requests ask for: ``(tenant, mated, pick)`` arrays.
    ``tenant`` indexes ``tenants`` (all 0 with no tenants), ``mated``
    says whether the request shows an enrolled subject, ``pick`` is a
    31-bit draw that the caller maps onto its bank of such requests."""
    rng = _rng(seed, 2, stream)
    shares = mix.get("tenant_shares")
    if shares:
        counts = _counts([shares[t] for t in tenants], n)
        tenant = np.repeat(np.arange(len(tenants)), counts)
    else:
        tenant = np.zeros(n, np.int64)
    mated = np.zeros(n, bool)
    mated[: int(round(mate_share * n))] = True
    rng.shuffle(tenant)
    rng.shuffle(mated)
    pick = rng.integers(0, 2 ** 31, n)
    return tenant, mated, pick
