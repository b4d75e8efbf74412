"""YCSB's request-key distributions and the benchmark's replayed event pool.

``zipfian`` is the bounded Zipfian generator of Gray et al. ("Quickly
generating billion-record synthetic databases", SIGMOD 1994) as YCSB's
``ZipfianGenerator`` implements it, and ``scrambled_zipfian`` is YCSB's
``ScrambledZipfianGenerator``: a Zipfian rank over 10^10 items hashed by
FNV-1a-64 onto the record count, so the hot keys are spread over the key
space.  numpy's own ``zipf`` needs an exponent above 1 and cannot give
YCSB's constant 0.99.

``build_pool`` packs the draws into the fixed blocks the benchmark replays:
each block holds fresh insertions, then retractions of the leading quarter
(``retract``) of the previous block's insertions, and the first block
retracts from the last one, so the pool can be replayed in a cycle and
every retraction cancels an insertion ingested before it: the harness
ingests the primer, the last block's insertions alone, in set-up.
Everything is a pure function of the seed.
"""
from __future__ import annotations

import numpy as np

ZIPFIAN_CONSTANT = 0.99
# ScrambledZipfianGenerator: ITEM_COUNT and the zeta it precomputes for it
SCRAMBLED_ITEMS = 10_000_000_000
SCRAMBLED_ZETAN = 26.46902820178302

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} 1 / i^theta, in float64 (YCSB's ``zetastatic``)."""
    total, lo, step = 0.0, 1, 1 << 22
    while lo <= n:
        i = np.arange(lo, min(n, lo + step - 1) + 1, dtype=np.float64)
        total += float(np.sum(i ** -theta))
        lo += step
    return total


class Zipfian:
    """YCSB's ``ZipfianGenerator`` over ranks ``[0, items)``."""

    def __init__(self, items: int, theta: float = ZIPFIAN_CONSTANT,
                 zetan: float | None = None):
        self.items = int(items)
        self.theta = float(theta)
        self.zetan = zeta(self.items, theta) if zetan is None else zetan
        self.alpha = 1.0 / (1.0 - theta)
        self.zeta2 = zeta(2, theta)
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - theta))
                    / (1.0 - self.zeta2 / self.zetan))

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """Ranks for uniform draws ``u`` in [0, 1), as YCSB's ``nextLong``."""
        uz = u * self.zetan
        body = np.floor(self.items * np.power(self.eta * u - self.eta + 1.0,
                                              self.alpha))
        r = np.where(uz < 1.0, 0.0,
                     np.where(uz < 1.0 + 0.5 ** self.theta, 1.0, body))
        return np.minimum(r, self.items - 1).astype(np.int64)

    def pmf(self, ranks: np.ndarray) -> np.ndarray:
        """The exact probability this generator gives each rank.

        Ranks 0 and 1 come from the two explicit branches; every rank
        from 2 on comes from the power law, whose CDF is
        ``1 - (1 - (x / items)^(1 - theta)) / eta`` below rank ``x``.
        """
        ranks = np.asarray(ranks, np.float64)
        p0 = 1.0 / self.zetan
        p1 = 0.5 ** self.theta / self.zetan

        def cdf(x):  # P(body rank < x) for the power-law branch, as P(u)
            return 1.0 - (1.0 - (x / self.items) ** (1.0 - self.theta)) / self.eta

        lo = np.maximum(cdf(ranks), p0 + p1)
        hi = np.maximum(cdf(ranks + 1.0), p0 + p1)
        body = np.clip(hi - lo, 0.0, None)
        return body + np.where(ranks == 0, p0, np.where(ranks == 1, p1, 0.0))


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV over the 8 low bytes, then abs."""
    v = np.asarray(v, np.int64).view(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * _FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


class ScrambledZipfian:
    """YCSB's ``ScrambledZipfianGenerator`` over keys ``[0, records)``."""

    def __init__(self, records: int, theta: float = ZIPFIAN_CONSTANT):
        self.records = int(records)
        zetan = SCRAMBLED_ZETAN if theta == ZIPFIAN_CONSTANT else None
        # YCSB builds ZipfianGenerator(0, ITEM_COUNT): ITEM_COUNT + 1 items
        self.gen = Zipfian(SCRAMBLED_ITEMS + 1, theta, zetan)

    def keys(self, u: np.ndarray) -> np.ndarray:
        return fnvhash64(self.gen.ranks(u)) % self.records


def rng_for(seed: int, *path: int) -> np.random.Generator:
    """An independent stream for ``path`` under the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *path]))


def build_pool(seed: int, streams: int, span: int, blocks: int,
               records: int, theta: float = ZIPFIAN_CONSTANT,
               retract: float = 0.25, start: int = 0):
    """The replayed pool: ``blocks`` (keys, values) pairs of (streams, span),
    and the primer, the last block with its retractions left out.

    Each row of a block is ``ins`` insertions (+1) of keys drawn from the
    scrambled Zipfian, then ``floor(ins * retract)`` retractions (-1) of the
    leading insertions of the previous block's same row (cyclically), then
    padding (key -1, value 0).  ``ins`` is the largest count for which both
    fit in ``span``.  Stream ``s`` draws from its own seeded sequence.
    The cycle is rotated to begin at block ``start``: the same blocks in
    another order, with the primer taken from the new last block.
    """
    ins = int(span / (1.0 + retract))
    while ins + int(ins * retract) > span:
        ins -= 1
    nret = int(ins * retract)
    gen = ScrambledZipfian(records, theta)
    inserts = np.empty((blocks, streams, ins), np.int32)
    for b in range(blocks):
        u = rng_for(seed, 1, b).random((streams, ins))
        inserts[b] = gen.keys(u)
    pool = []
    for b in range(blocks):
        keys = np.full((streams, span), -1, np.int32)
        vals = np.zeros((streams, span), np.float32)
        keys[:, :ins] = inserts[b]
        vals[:, :ins] = 1.0
        keys[:, ins:ins + nret] = inserts[b - 1, :, :nret]
        vals[:, ins:ins + nret] = -1.0
        pool.append((keys, vals))
    start %= blocks
    pool = pool[start:] + pool[:start]
    keys, vals = (x.copy() for x in pool[-1])
    keys[:, ins:], vals[:, ins:] = -1, 0.0
    return pool, (keys, vals)
