"""Pallas TPU kernel: batched turnstile sparse scatter-update.

The dense update kernel (``countsketch_update``) sketches contiguous key
segments (``values[i]`` <-> ``base_key + i``).  The actual streaming model of
the paper is the TURNSTILE one: arbitrary batches of signed ``(key, +-value)``
updates, including deletions.  This kernel ingests those directly:

    for each (keys, values) block  (B, N)  streamed HBM -> VMEM:
        r_x     = D[hash(key)]                 (VPU, fused transform Eq. 5;
                                                D = Exp[1] ppswor / U(0,1]
                                                priority per static scheme)
        for each sketch row r:
            bucket_r = hash_r(key) mod W       (VPU multiply-shift)
            onehot   = (bucket_r == col_ids)   (B, N, WB) in VREGs
            table[r] += (sign_r * v / r_x^{1/p}) @ onehot   (batched MXU)

TPUs have no atomics, so -- exactly like the dense kernel -- the scatter is a
ONE-HOT MATMUL: duplicate keys inside a block each contribute their own
one-hot row and the MXU contraction sums them, which is the scatter-add.

Padding/raggedness: a slot is ignored when its position is past the stream's
``lengths[b]`` OR its key is -1 (the library-wide ``_EMPTY`` padding key), so
ragged microbatch concatenations feed straight in.

Grid: (batch_blocks, width_blocks, n_blocks), n innermost => each
(stream-block, width-block) table tile stays resident in VMEM across the
whole element sweep; per-stream seeds/transform-seeds/lengths ride in a
(B, 128) meta table.  This is the SketchEngine sparse-ingest data plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import transforms
from repro.core import hashing

# meta-table layout + broadcast prologue shared with the dense kernel:
# defined ONCE in countsketch_update.py so the two data planes cannot
# desynchronize (the scatter kernel simply never reads _META_BASE); the
# block/padding arithmetic is the library-wide tiling helper.
from . import tiling
from .onehot import onehot_dot
from .countsketch_update import (
    _META_COLS,
    _META_N,
    _META_SEED,
    _META_TSEED,
    _broadcast_stream_params,
    _stream_meta,
)


def _batched_kernel(meta_ref, keys_ref, vals_ref, table_ref, *, rows: int,
                    width: int, block_n: int, block_w: int, p: float | None,
                    scheme: str):
    # grid = (batch_blocks, width_blocks, n_blocks); n innermost so each
    # (stream-block, width-block) table tile accumulates over the stream.
    j = pl.program_id(1)  # width block
    i = pl.program_id(2)  # element block

    @pl.when(i == 0)
    def _init():
        table_ref[...] = jnp.zeros_like(table_ref)

    seed = meta_ref[:, _META_SEED:_META_SEED + 1].astype(jnp.uint32)   # (B,1)
    tseed = meta_ref[:, _META_TSEED:_META_TSEED + 1].astype(jnp.uint32)
    n_valid = meta_ref[:, _META_N:_META_N + 1]                         # (B,1)

    keys_raw = keys_ref[...]                  # (B, N) int32, -1 = padding
    vals = vals_ref[...].astype(jnp.float32)  # (B, N) signed
    offs = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)           # (1, N)
    valid = (offs < n_valid) & (keys_raw != jnp.int32(-1))  # (B, N)
    keys = keys_raw.astype(jnp.uint32)

    if p is not None:
        # Fused bottom-k transform (Eq. 5): v -> v / r_x^{1/p}; the
        # randomizer dispatch is static, so either scheme traces into the
        # kernel body as pure VPU ops.
        r_x = transforms.randomizer(keys, tseed, scheme)
        vals = vals * r_x ** jnp.float32(-1.0 / p)
    vals = jnp.where(valid, vals, 0.0)

    col0 = j * block_w
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_w), 1) + col0

    contribs = []
    for r in range(rows):
        salt = hashing.row_salt(seed, jnp.uint32(r))          # (B, 1)
        bucket = hashing.bucket_hash(keys, salt, width)       # (B, N)
        sign = hashing.sign_hash(keys, salt)                  # (B, N)
        sv = (sign * vals)[:, None, :]                        # (B, 1, N)
        onehot = (bucket[:, :, None] == cols[None]).astype(jnp.float32)
        # batched contraction: B streams on the MXU -> (B, 1, WB)
        contribs.append(onehot_dot(sv, onehot, (((2,), (1,)), ((0,), (0,)))))
    table_ref[...] += jnp.concatenate(contribs, axis=1)  # (B, rows, WB)


@functools.partial(
    jax.jit,
    static_argnames=("rows", "width", "p", "scheme", "block_n", "block_w",
                     "block_b", "interpret"),
)
def countsketch_scatter_batched(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    rows: int,
    width: int,
    seeds: jnp.ndarray,
    p: float | None = None,
    scheme: str = transforms.PPSWOR,
    transform_seeds=None,
    lengths=None,
    block_n: int = tiling.BLOCK_N,
    block_w: int = tiling.BLOCK_W,
    block_b: int = tiling.BLOCK_B,
    interpret: bool = True,
) -> jnp.ndarray:
    """Scatter B sparse signed streams in ONE pallas_call; (B, rows, width).

    ``keys``/``values`` are (B, n) int32 / float32: stream b's update batch
    is ``(keys[b, i], values[b, i])`` for ``i < lengths[b]``; values may be
    negative (turnstile deletions) and duplicate keys accumulate.  Slots
    with ``keys == -1`` are padding regardless of ``lengths``.  With ``p``
    set, the bottom-k transform of ``scheme`` is fused (ppswor Exp[1] or
    priority U(0,1] randomizer).  The batch tiles as
    ``tiling.batch_block``: fewer than ``SUBLANE`` streams are one block of
    exactly B rows, more pad to a whole number of ``block_b`` blocks.
    """
    B, n = keys.shape
    assert values.shape == (B, n), (keys.shape, values.shape)
    seeds, transform_seeds, lengths = _broadcast_stream_params(
        B, n, seeds, transform_seeds, lengths)

    block_w, w_pad = tiling.fit_block(block_w, width)
    (block_b, b_pad), (block_n, n_pad) = tiling.scatter_tiles(
        B, n, block_b, block_n)

    # padded slots get key -1 => masked inside the kernel
    keys_p = jnp.pad(jnp.asarray(keys, jnp.int32),
                     ((0, b_pad - B), (0, n_pad - n)), constant_values=-1)
    vals_p = jnp.pad(values.astype(jnp.float32),
                     ((0, b_pad - B), (0, n_pad - n)))
    meta = _stream_meta(b_pad, seeds, transform_seeds, lengths)

    grid = (b_pad // block_b, w_pad // block_w, n_pad // block_n)
    table = pl.pallas_call(
        functools.partial(_batched_kernel, rows=rows, width=width,
                          block_n=block_n, block_w=block_w, p=p,
                          scheme=scheme),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, _META_COLS), lambda b, j, i: (b, 0)),
            pl.BlockSpec((block_b, block_n), lambda b, j, i: (b, i)),
            pl.BlockSpec((block_b, block_n), lambda b, j, i: (b, i)),
        ],
        out_specs=pl.BlockSpec((block_b, rows, block_w),
                               lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, rows, w_pad), jnp.float32),
        interpret=interpret,
        name="worp_countsketch_scatter_batched",
    )(meta, keys_p, vals_p)
    return table[:B, :, :width]


def countsketch_scatter(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    rows: int,
    width: int,
    seed,
    p: float | None = None,
    scheme: str = transforms.PPSWOR,
    transform_seed=0,
    interpret: bool = True,
    **kw,
) -> jnp.ndarray:
    """Single-stream convenience wrapper: (n,) keys/values -> (rows, width)."""
    table = countsketch_scatter_batched(
        jnp.asarray(keys, jnp.int32)[None, :],
        jnp.asarray(values, jnp.float32)[None, :],
        rows, width,
        jnp.asarray(seed, jnp.uint32)[None],
        p=p, scheme=scheme,
        transform_seeds=jnp.asarray(transform_seed, jnp.uint32)[None],
        interpret=interpret, **kw)
    return table[0]
