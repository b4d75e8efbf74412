"""Stateless, seed-keyed hashing primitives for WORp sketches.

Everything here is a pure function of (key, salt): the same key always maps to
the same random variate, across hosts, shards and passes.  This is the property
the paper relies on for composability -- the p-ppswor transform (Eq. 5) and the
CountSketch row hashes must agree between sketches that are later merged.

TPU adaptation: we use an invertible 32-bit integer mixer ("lowbias32") built
from multiplies and xor-shifts only -- no lookup tables, no gathers -- so hashing
runs on the VPU at full rate and fuses into the Pallas sketch-update kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Murmur3/lowbias32-style finalizer constants.  numpy scalars (NOT jnp
# arrays): they must inline as literals when the hash is traced inside a
# Pallas kernel body -- captured jnp-array constants are rejected by
# pallas_call, and bare Python ints > 2^31-1 overflow weak int32 typing.
import numpy as _np

_M1 = _np.uint32(0x7FEB352D)
_M2 = _np.uint32(0x846CA68B)
# Distinct stream constants (large odd).
_ROW_SALT = _np.uint32(0x9E3779B9)  # golden-ratio increment per sketch row
_SIGN_SALT = _np.uint32(0x85EBCA6B)
_EXP_SALT = _np.uint32(0xC2B2AE35)


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Low-bias 32-bit integer finalizer (avalanching mixer)."""
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def hash_u32(keys: jnp.ndarray, salt) -> jnp.ndarray:
    """Hash integer keys to uniform uint32, keyed by ``salt``."""
    k = jnp.asarray(keys, jnp.uint32)
    s = jnp.asarray(salt, jnp.uint32)
    # Two rounds with salt injection between them: empirically enough to
    # decorrelate consecutive integer keys (the common case: parameter indices).
    return _mix32(_mix32(k + s) ^ (s * _ROW_SALT))


def uniform01(keys: jnp.ndarray, salt) -> jnp.ndarray:
    """Uniform(0, 1] float32 from a hash; strictly positive (safe for log)."""
    h = hash_u32(keys, jnp.asarray(salt, jnp.uint32) ^ _EXP_SALT)
    # Use the top 24 bits -> exactly representable in float32; add 2^-25 so the
    # value is never 0.  The int32 hop is exact (the value is below 2^24) and
    # lets the cast lower in Mosaic, which has no uint32 -> float32 cast.
    u = (h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0**-24)
    return u + jnp.float32(2.0**-25)


def exp1(keys: jnp.ndarray, salt) -> jnp.ndarray:
    """Per-key Exp[1] variate r_x (the ppswor randomization, Sec. 2.1).

    The top bin's midpoint 1 - 2^-25 rounds to 1.0 in float32, whose log is
    0 and would make v / r_x^{1/p} infinite; that bin gets its midpoint's
    variate, -log(1 - 2^-25) = 2^-25 in float32.  Every other bin's -log is
    above 2^-25, so the floor changes nothing else.
    """
    return jnp.maximum(-jnp.log(uniform01(keys, salt)), jnp.float32(2.0**-25))


def sign_hash(keys: jnp.ndarray, salt) -> jnp.ndarray:
    """Rademacher +-1 (float32), keyed by ``salt`` (CountSketch sign hash)."""
    h = hash_u32(keys, jnp.asarray(salt, jnp.uint32) ^ _SIGN_SALT)
    return jnp.where((h & jnp.uint32(1)) == 0, jnp.float32(1), jnp.float32(-1))


def bucket_hash(keys: jnp.ndarray, salt, width: int) -> jnp.ndarray:
    """Bucket id in [0, width) (CountSketch bucket hash).

    ``width`` need not be a power of two; modulo bias is O(width / 2^32),
    negligible for any practical sketch width.
    """
    h = hash_u32(keys, salt)
    return (h % jnp.uint32(width)).astype(jnp.int32)


def row_salt(seed, row) -> jnp.ndarray:
    """Per-row salt for multi-row sketches: decorrelated via golden-ratio step."""
    seed = jnp.asarray(seed, jnp.uint32)
    row = jnp.asarray(row, jnp.uint32)
    return seed + (row + jnp.uint32(1)) * _ROW_SALT


def key_hash_to_domain(keys: jnp.ndarray, salt, n: int) -> jnp.ndarray:
    """KeyHash: map arbitrary (integer-encoded) keys into [n] (paper Eq. 13)."""
    return (hash_u32(keys, salt) % jnp.uint32(n)).astype(jnp.int32)


_SHARD_SALT = _np.uint32(0x5A17AB1E)  # dedicated stream-partition salt


def _mix32_np(x: "_np.ndarray") -> "_np.ndarray":
    """Host-side numpy mirror of ``_mix32`` (bit-identical on uint32)."""
    x = _np.asarray(x, _np.uint32)
    x = x ^ (x >> _np.uint32(16))
    x = (x * _M1).astype(_np.uint32)
    x = x ^ (x >> _np.uint32(15))
    x = (x * _M2).astype(_np.uint32)
    x = x ^ (x >> _np.uint32(16))
    return x


def hash_u32_np(keys, salt) -> "_np.ndarray":
    """Host-side numpy mirror of ``hash_u32``, bit-identical by test
    (test_turnstile), so host-side partitioning decisions agree with any
    device-side replay of the same hash."""
    with _np.errstate(over="ignore"):
        k = _np.asarray(keys, _np.uint32)
        s = _np.uint32(salt)
        return _mix32_np(_mix32_np((k + s).astype(_np.uint32)) ^
                         _np.uint32(s * _ROW_SALT))


def shard_of_keys(keys, num_shards: int) -> "_np.ndarray":
    """Per-key shard id in ``[0, num_shards)`` for stream partitioning.

    Pure function of the key alone (dedicated salt, no dependence on shard
    count beyond the final modulo), so a key's updates -- insertions AND the
    deletions that later retract them -- always land on the same shard, and
    the union of all shards' events is the same multiset for every S.  This
    is what makes sharded ingestion mergeable in the paper's sense: each
    shard sketches a disjoint sub-stream and the composable merge restores
    the full-stream sketch exactly.
    """
    if num_shards <= 1:
        return _np.zeros(_np.shape(keys), _np.int64)
    h = hash_u32_np(keys, _SHARD_SALT)
    return (h % _np.uint32(num_shards)).astype(_np.int64)


def seeds_concretely_differ(a, b) -> bool:
    """True when two seed arrays are concretely known to differ.

    The composability contract (module docstring) requires merged shards to
    share seeds; this is the mergeability check's primitive.  Inside
    jit/vmap seeds are tracers and cannot be inspected -- the check degrades
    to a no-op there (the engine layer validates configs instead);
    host-side merges of concrete states get the full check.
    """
    try:
        return bool(jnp.any(jnp.asarray(a) != jnp.asarray(b)))
    except jax.errors.ConcretizationTypeError:
        return False
