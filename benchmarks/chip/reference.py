"""The plain reference: WORp's CountSketch semantics in float64 numpy.

It imports nothing of the program.  It restates, from the paper and the
program's documented hash family, what a one-pass WORp state must hold
after a turnstile stream:

* the bottom-k transform (paper Eq. 5): an event ``(x, v)`` contributes
  ``v / r_x^(1/p)``, with ``r_x`` Exp[1] (ppswor) or U(0, 1] (priority),
  drawn from a 32-bit hash of ``x`` under the stream's transform seed;
* CountSketch: row ``r`` adds ``sign_r(x) * v*`` into bucket ``h_r(x)``
  (``lowbias32`` mixer, two rounds, golden-ratio row salts);
* the estimate of a key is the median over rows of its signed buckets.

Linearity makes the table a function of each key's net frequency, so the
reference sums frequencies per key exactly and transforms each key once.
"""
from __future__ import annotations

import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_ROW_SALT = np.uint32(0x9E3779B9)
_SIGN_SALT = np.uint32(0x85EBCA6B)
_EXP_SALT = np.uint32(0xC2B2AE35)


def _mix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(15))
    x = x * _M2
    return x ^ (x >> np.uint32(16))


def hash_u32(keys: np.ndarray, salt) -> np.ndarray:
    with np.errstate(over="ignore"):
        k = np.asarray(keys).astype(np.uint32)
        s = np.uint32(salt)
        return _mix32(_mix32(k + s) ^ np.uint32(s * _ROW_SALT))


def uniform01(keys, salt) -> np.ndarray:
    """U(0, 1]: the hash's top 24 bits, shifted by half a bin (float32)."""
    h = hash_u32(keys, np.uint32(salt) ^ _EXP_SALT)
    return ((h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)
            + np.float32(2.0 ** -25))


def randomizer(keys, seed, scheme: str) -> np.ndarray:
    """r_x as float64.  Exp[1] is floored at 2^-25, the top bin's variate."""
    u = uniform01(keys, seed)
    if scheme == "ppswor":
        return np.maximum(-np.log(u.astype(np.float64)), 2.0 ** -25)
    if scheme == "priority":
        return u.astype(np.float64)
    raise ValueError(f"unknown scheme {scheme!r}")


def row_salt(seed, row: int) -> np.uint32:
    with np.errstate(over="ignore"):
        return np.uint32(np.uint32(seed) + np.uint32(row + 1) * _ROW_SALT)


def buckets_signs(keys, seed, rows: int, width: int):
    """(rows, n) bucket ids and +-1 signs."""
    b = np.empty((rows, np.size(keys)), np.int64)
    s = np.empty((rows, np.size(keys)), np.float64)
    for r in range(rows):
        salt = row_salt(seed, r)
        b[r] = hash_u32(keys, salt) % np.uint32(width)
        s[r] = np.where((hash_u32(keys, salt ^ _SIGN_SALT) & 1) == 0, 1.0, -1.0)
    return b, s


def table(keys, freqs, seed, tseed, rows: int, width: int, p: float,
          scheme: str) -> np.ndarray:
    """The (rows, width) float64 table of distinct ``keys`` with net
    frequencies ``freqs``."""
    keys = np.asarray(keys)
    tv = np.asarray(freqs, np.float64) * randomizer(keys, tseed, scheme) ** (-1.0 / p)
    b, s = buckets_signs(keys, seed, rows, width)
    out = np.empty((rows, width), np.float64)
    for r in range(rows):
        out[r] = np.bincount(b[r], weights=s[r] * tv, minlength=width)
    return out


def mass(keys, events, seed, tseed, rows: int, width: int, p: float,
         scheme: str) -> np.ndarray:
    """The (rows, width) sum of |contribution| over every event each cell
    received, for distinct ``keys`` with ``events`` events each (insertions
    and retractions alike): the magnitude an fp32 sum of the cell passes
    through, against which its rounding is measured."""
    keys = np.asarray(keys)
    tv = np.asarray(events, np.float64) * randomizer(keys, tseed, scheme) ** (-1.0 / p)
    b, _ = buckets_signs(keys, seed, rows, width)
    out = np.empty((rows, width), np.float64)
    for r in range(rows):
        out[r] = np.bincount(b[r], weights=tv, minlength=width)
    return out


def estimate(tab: np.ndarray, keys, seed) -> np.ndarray:
    """Median-over-rows estimates of ``keys`` against a reference table."""
    rows, width = tab.shape
    b, s = buckets_signs(keys, seed, rows, width)
    return np.median(tab[np.arange(rows)[:, None], b] * s, axis=0)


_SHARD_SALT = np.uint32(0x5A17AB1E)


def shard_of(keys, shards: int) -> np.ndarray:
    """The shard a key is routed to: its hash under the routing salt, modulo
    the shard count; a pure function of the key, so a retraction lands on
    the shard that holds its insertion."""
    if shards <= 1:
        return np.zeros(np.shape(keys), np.int64)
    return (hash_u32(keys, _SHARD_SALT) % np.uint32(shards)).astype(np.int64)


class OnePass:
    """The one-pass candidate policy of one stream, restated in float64.

    After each flush, each shard keeps the ``capacity`` keys of largest
    |estimate| on its own table among its candidates and the flush's keys
    routed to it (ties to the smaller key).  A read collapses the shards in
    order, each merge keeping the ``capacity`` keys of largest |estimate| on
    the summed table among both candidate sets.  A key evicted early can
    later grow, by collisions, above keys that were kept; the policy, not
    every key's estimate, says which keys a sound sampler holds.
    """

    def __init__(self, seed, tseed, rows: int, width: int, p: float,
                 scheme: str, capacity: int, shards: int = 1):
        self.seed, self.tseed, self.p, self.scheme = seed, tseed, p, scheme
        self.rows, self.width, self.capacity = rows, width, capacity
        self.shards = max(int(shards), 1)
        self.tabs = np.zeros((self.shards, rows, width))
        self.cands = [np.empty(0, np.int64) for _ in range(self.shards)]

    def _top(self, tab, keys) -> np.ndarray:
        keys = np.unique(keys[keys >= 0])
        est = np.abs(estimate(tab, keys, self.seed))
        return keys[np.argsort(-est, kind="stable")[:self.capacity]]

    def flush(self, keys, vals) -> None:
        live = np.asarray(keys) >= 0
        keys = np.asarray(keys)[live].astype(np.int64)
        vals = np.asarray(vals)[live].astype(np.float64)
        tv = vals * randomizer(keys, self.tseed, self.scheme) ** (-1.0 / self.p)
        b, s = buckets_signs(keys, self.seed, self.rows, self.width)
        sid = shard_of(keys, self.shards)
        for sh in np.unique(sid):
            m = sid == sh
            for r in range(self.rows):
                self.tabs[sh, r] += np.bincount(b[r, m], weights=s[r, m] * tv[m],
                                                minlength=self.width)
            self.cands[sh] = self._top(self.tabs[sh],
                                       np.concatenate([self.cands[sh], keys[m]]))

    def collapse(self) -> np.ndarray:
        """The candidates a read holds."""
        tab, cand = self.tabs[0].copy(), self.cands[0]
        for sh in range(1, self.shards):
            tab += self.tabs[sh]
            cand = self._top(tab, np.concatenate([cand, self.cands[sh]]))
        return cand


class StreamCounts:
    """Net per-key frequencies of one stream's pool blocks.

    ``freqs(mult)`` gives the net frequency of every distinct pool key
    when block ``b`` has been ingested ``mult[b]`` times.
    """

    def __init__(self, blocks_keys, blocks_vals):
        live = [k >= 0 for k in blocks_keys]
        allk = np.concatenate([k[m] for k, m in zip(blocks_keys, live)])
        self.keys, inv = np.unique(allk, return_inverse=True)
        self._per_block, self._abs_per_block = [], []
        at = 0
        for k, v, m in zip(blocks_keys, blocks_vals, live):
            n = int(m.sum())
            w = v[m].astype(np.float64)
            self._per_block.append(np.bincount(
                inv[at:at + n], weights=w, minlength=self.keys.size))
            self._abs_per_block.append(np.bincount(
                inv[at:at + n], weights=np.abs(w), minlength=self.keys.size))
            at += n

    @staticmethod
    def _sum(mult, per_block) -> np.ndarray:
        out = np.zeros(per_block[0].size, np.float64)
        for m, f in zip(mult, per_block):
            if m:
                out += m * f
        return out

    def freqs(self, mult) -> np.ndarray:
        return self._sum(mult, self._per_block)

    def events(self, mult) -> np.ndarray:
        """Events of every distinct key, insertions and retractions alike."""
        return self._sum(mult, self._abs_per_block)
