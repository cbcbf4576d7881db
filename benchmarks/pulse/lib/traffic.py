"""Data and traffic from ``--seed``: the one generator every mix file drives.

The key-value data and the YCSB key chooser follow ``chip_smoke.py``'s
(``make_data``, ``zipf_picker``, ``new_value``): present keys are drawn
without replacement below ``ABSENT_LO`` and absent keys above it, values are
int32, and zipfian popularity ranks are scattered over the key space as
YCSB scrambles them.

Every seed gets the same amount of work in another order: a batch of ``n``
requests holds exactly ``round(share * n)`` requests of each operation and
``round(miss_rate * finds)`` finds of absent keys, its popularity ranks are
the same ``n`` quantiles of the zipfian distribution, and an open loop's
inter-arrival gaps are the same ``n`` quantiles of the exponential
distribution, scaled to fill the window exactly.  Where the keys' depths
differ (a hash chain), each popularity rank also takes a key of the same
depth quantile for every seed, read from the arena as built: the few
hottest keys take a third of the requests, so a seed whose hot keys sat
deep in their chains would otherwise send more work.  The quantiles are
stratified (``depth_quantiles``), so the popularity-weighted depth is the
keys' own mean depth, as the average over seeds of unmatched ranks would
be.  The seed picks which keys exist, which key of the rank's depth holds
it, and the order of everything.
"""

from __future__ import annotations

import dataclasses

import numpy as np

ABSENT_LO = 1 << 30  # present keys are drawn below this, absent ones above
KEY_HI = (1 << 31) - 2
OPS = ("find", "update")
FIND, UPDATE = 0, 1


def make_data(n_keys: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """``n_keys`` distinct present keys and their int32 values."""
    keys = rng.choice(ABSENT_LO, n_keys, replace=False).astype(np.int32)
    values = rng.integers(0, 1 << 31, n_keys, dtype=np.int64).astype(np.int32)
    return keys, values


def new_value(req_id: int, seed: int) -> int:
    """The value update request ``req_id`` writes: every update its own, so
    the order in which two updates of one key took effect shows."""
    return int((req_id * 2654435761 + seed * 40503 + 1) % (1 << 31))


BALANCED_RANKS = 1 << 16  # the hottest ranks, whose quantile pairs are oriented


def depth_quantiles(pmf: np.ndarray, sorted_depths: np.ndarray) -> np.ndarray:
    """The depth quantile of each popularity rank, given the ranks' shares of
    the requests (``pmf``, hottest first) and the keys' depths in ascending
    order: the van der Corput sequence in base 2 (0.5, 0.25, 0.75, 0.125,
    0.625, ...), so every block of consecutive ranks spreads evenly over the
    depths, with each consecutive pair of the hottest ranks given its two
    quantiles in the order that keeps the share-weighted depth nearest the
    keys' mean depth."""
    n = len(pmf)
    k = np.arange(1, n + 1, dtype=np.int64)
    q = np.zeros(n)
    f = 0.5
    while k.any():
        q += (k & 1) * f
        k >>= 1
        f /= 2
    dev = sorted_depths[np.minimum((q * n).astype(np.int64), n - 1)] - sorted_depths.mean()
    bias = pmf[0] * dev[0]
    for r in range(1, min(n, BALANCED_RANKS) - 1, 2):
        keep = bias + pmf[r] * dev[r] + pmf[r + 1] * dev[r + 1]
        swap = bias + pmf[r] * dev[r + 1] + pmf[r + 1] * dev[r]
        if abs(swap) < abs(keep):
            q[r], q[r + 1] = q[r + 1], q[r]
            keep = swap
        bias = keep
    return q


def zipf_cdf(n_keys: int, s: float) -> np.ndarray:
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -s
    return np.cumsum(p / p.sum())


@dataclasses.dataclass
class Batch:
    """``n`` requests in submission order (parallel arrays)."""

    op: np.ndarray  # FIND / UPDATE
    key: np.ndarray  # int32
    value: np.ndarray  # int32 payload of updates (0 for finds)
    req_id: np.ndarray  # int64, unique over the run


class Generator:
    """Draws batches of requests of one mix over one data set."""

    def __init__(self, mix: dict, cfg: dict, keys: np.ndarray, rank_rng, seed: int,
                 depths: np.ndarray | None = None):
        self.seed = seed
        self.shares = {op: float(mix["ops"].get(op, 0.0)) for op in OPS}
        unknown = set(mix["ops"]) - set(OPS)
        if unknown:
            raise ValueError(f"unknown operations {sorted(unknown)} in the mix")
        if abs(sum(self.shares.values()) - 1.0) > 1e-9:
            raise ValueError(f"operation shares sum to {sum(self.shares.values())}")
        self.miss_rate = float(cfg["miss_rate"])
        self.cdf = zipf_cdf(len(keys), float(cfg["zipf_constant"]))
        # which key holds each popularity rank (YCSB scrambles the ranks)
        perm = rank_rng.permutation(len(keys))
        if depths is not None:
            # keys by depth, in the seed's order within a depth; the ranks,
            # in the order of their depth quantiles, take them in turn
            depths = np.asarray(depths)
            by_depth = perm[np.argsort(depths[perm], kind="stable")]
            quantiles = depth_quantiles(np.diff(self.cdf, prepend=0.0), depths[by_depth])
            perm = np.empty_like(by_depth)
            perm[np.argsort(quantiles, kind="stable")] = by_depth
        self.ranked = np.asarray(keys)[perm]
        self.next_id = 0

    def _ranks(self, n: int, rng) -> np.ndarray:
        u = (np.arange(n) + 0.5) / n
        rng.shuffle(u)
        idx = np.searchsorted(self.cdf, u, side="right")
        return np.minimum(idx, len(self.ranked) - 1)

    def batch(self, n: int, rng) -> Batch:
        n_upd = int(round(self.shares["update"] * n))
        op = np.full(n, FIND, np.int8)
        op[:n_upd] = UPDATE
        rng.shuffle(op)
        key = self.ranked[self._ranks(n, rng)].astype(np.int32)
        finds = np.flatnonzero(op == FIND)
        n_miss = int(round(self.miss_rate * len(finds)))
        miss = rng.choice(finds, n_miss, replace=False)
        key[miss] = rng.integers(ABSENT_LO, KEY_HI, n_miss, dtype=np.int64)
        req_id = np.arange(self.next_id, self.next_id + n, dtype=np.int64)
        self.next_id += n
        value = np.zeros(n, np.int32)
        for i in np.flatnonzero(op == UPDATE):
            value[i] = new_value(int(req_id[i]), self.seed)
        return Batch(op, key, value, req_id)

    def readback(self, keys) -> Batch:
        """One find of each of ``keys`` (the read-back after the window)."""
        keys = np.asarray(sorted(set(int(k) for k in keys)), np.int32)
        n = len(keys)
        req_id = np.arange(self.next_id, self.next_id + n, dtype=np.int64)
        self.next_id += n
        return Batch(np.full(n, FIND, np.int8), keys, np.zeros(n, np.int32), req_id)


def arrival_offsets(rate_rps: float, seconds: float, rng) -> np.ndarray:
    """Due times, in seconds from the window's start, of a Poisson stream at
    ``rate_rps``: ``round(rate * seconds)`` exponential quantile gaps in the
    seed's order, scaled so the last request is due at the window's end."""
    n = max(1, int(round(rate_rps * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    rng.shuffle(gaps)
    due = np.cumsum(gaps)
    return due * (seconds / due[-1])
