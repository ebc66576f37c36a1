"""The one traffic generator: it reads every traffic file.

A traffic file, ``perfbench/traffic/<name>.json``, holds parameters only:

``loop``
    ``"open"``: requests arrive on a schedule whatever the server does, as
    independent users send them.
``k``
    Answers per request.
``lanes``, ``lane_shares``
    The query widths (Lq buckets) the server compiles, and the share of
    requests in each.
``stream_seed``
    The fixed stream that arrival instants and lanes come from. ``--seed``
    never reaches it, so every seed gets the same arrivals and lanes.
``rate_qps``
    The Poisson arrival rate.
``batch_shapes``, ``max_wait_s``, ``deadline_ms``, ``degrade_rho``
    The admission queue's flush shapes and policy.
``check_sample``
    How many served answers the reference checks after the window.

``--seed`` picks the collection, and with it each lane's pool: the seed's
queries whose width falls in that lane. A request takes the next query of
its lane's pool, and a pool that runs out starts again from its first query.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

_OPEN = {"loop", "k", "lanes", "lane_shares", "stream_seed", "rate_qps", "batch_shapes",
         "max_wait_s", "deadline_ms", "degrade_rho", "check_sample"}
_CHUNK = 4096  # draws per refill of a stream; the streams never depend on the window


def load(path: str | Path) -> dict:
    """Read and validate one traffic file."""
    t = json.loads(Path(path).read_text())
    if t.get("loop") != "open":
        raise ValueError(f"{path}: loop must be 'open', got {t.get('loop')!r}")
    missing = _OPEN - set(t)
    if missing:
        raise ValueError(f"{path}: missing {sorted(missing)}")
    lanes, shares = t["lanes"], t["lane_shares"]
    if len(lanes) != len(shares) or list(lanes) != sorted(set(lanes)):
        raise ValueError(f"{path}: lanes must ascend, one share each")
    if abs(sum(shares) - 1.0) > 1e-9 or min(shares) < 0:
        raise ValueError(f"{path}: lane_shares must be a distribution, got {shares}")
    return t


def _streams(traffic: dict):
    ss = np.random.SeedSequence(int(traffic["stream_seed"]))
    return [np.random.default_rng(s) for s in ss.spawn(2)]


def _lane_draws(rng, shares, n: int) -> np.ndarray:
    out = [rng.choice(len(shares), size=_CHUNK, p=shares) for _ in range(-(-n // _CHUNK))]
    return np.concatenate(out)[:n] if out else np.zeros(0, np.int64)


def arrivals(traffic: dict, seconds: float) -> tuple[np.ndarray, np.ndarray]:
    """(arrival instants in [0, seconds), lane index of each): a Poisson
    process at ``rate_qps`` from the fixed stream. A longer window extends a
    shorter one's schedule and changes none of it."""
    r_time, r_lane = _streams(traffic)
    gaps = np.zeros(0)
    while gaps.sum() < seconds:
        gaps = np.concatenate([gaps, r_time.exponential(1.0 / float(traffic["rate_qps"]), _CHUNK)])
    t = np.cumsum(gaps)
    t = t[t < seconds]
    return t, _lane_draws(r_lane, traffic["lane_shares"], t.size)


def lane_pools(query_weights, lanes) -> list[list[int]]:
    """The seed's queries in each lane, by their number of live terms.

    A query's width is its last live slot plus one, as the admission queue
    counts it. A query wider than the widest lane belongs to no lane and is
    left out.
    """
    pools: list[list[int]] = [[] for _ in lanes]
    for i, w in enumerate(query_weights):
        live = np.flatnonzero(np.asarray(w) > 0)
        width = int(live[-1]) + 1 if live.size else 1
        lane = int(np.searchsorted(lanes, width))
        if lane < len(lanes):
            pools[lane].append(i)
    for lane, pool in zip(lanes, pools):
        if not pool:
            raise ValueError(f"the seed's query pool has no query of width <= {lane}")
    return pools


def fill(lane_idx: np.ndarray, pools: list[list[int]]) -> np.ndarray:
    """Query id of each scheduled request (or batch row): its lane's pool in
    order, started again from the top when it runs out."""
    seen = np.zeros(len(pools), dtype=np.int64)
    out = np.empty(len(lane_idx), dtype=np.int64)
    for j, lane in enumerate(lane_idx):
        pool = pools[lane]
        out[j] = pool[seen[lane] % len(pool)]
        seen[lane] += 1
    return out
