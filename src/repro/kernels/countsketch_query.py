"""Pallas TPU kernel: CountSketch query (per-row estimates for a key batch).

Estimating k keys needs table[r, bucket_r(key)] for every row r -- a gather on
GPU.  TPU adaptation: the gather becomes one-hot contractions on the MXU.
The final median-over-rows is O(R*K) and runs outside the kernel (ops layer).

Single-stream variant (``countsketch_query``): a one-hot matmul over width
blocks, each (K,) accumulator tile resident in VMEM across the width sweep:

    est_r  =  sum_j  onehot_j(keys) @ table[r, j*WB:(j+1)*WB]^T

Batched variant (``countsketch_query_batched``): the grid grows a leading
batch dimension so the B streams of a ``SketchEngine`` -- each with its own
table and hash seed -- are estimated by ONE ``pallas_call`` instead of a
Python loop of B dispatches.  Per-stream seeds ride in a (B, 128) meta
table.  It reads each bucket once, with no width sweep: a table row is
viewed as (H, 128) and ``bucket = 128 * hi + lo``, so

    part_r  =  table_r^T @ onehot(hi)      (128, H) @ (H, K)  on the MXU
    est_r   =  sum_lanes where(lane == lo, part_r, 0)          on the VPU

Each output sums one nonzero term, so it equals the table cell bitwise.
The grid is (batch blocks, key blocks), and a stream block's table stays in
VMEM across its key blocks.  This is the engine's batched estimate / sample
/ candidate-refresh query plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing

from . import tiling
from .onehot import onehot_dot
from .tiling import pad_to as _pad_to


def _kernel(meta_ref, keys_ref, table_ref, out_ref, *, rows: int, width: int,
            block_w: int):
    j = pl.program_id(0)

    seed = meta_ref[0].astype(jnp.uint32)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...].astype(jnp.uint32)  # (1, K)
    col0 = j * block_w
    # transposed one-hot (WB, K): the table row is the (1, WB) lhs
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_w, 1), 0) + col0

    ests = []
    for r in range(rows):
        salt = hashing.row_salt(seed, jnp.uint32(r))
        bucket = hashing.bucket_hash(keys, salt, width)  # (1, K)
        sign = hashing.sign_hash(keys, salt)             # (1, K)
        onehot = (bucket == cols).astype(jnp.float32)    # (WB, K)
        trow = table_ref[r:r + 1, :].astype(jnp.float32)  # (1, WB)
        part = onehot_dot(trow, onehot, (((1,), (0,)), ((), ())))  # (1, K)
        ests.append(part * sign)
    out_ref[...] += jnp.concatenate(ests, axis=0)  # (rows, K)


@functools.partial(
    jax.jit, static_argnames=("block_w", "interpret")
)
def countsketch_query(
    table: jnp.ndarray,
    keys: jnp.ndarray,
    seed,
    block_w: int = tiling.SINGLE_BLOCK_W,
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-row signed bucket reads: returns (rows, k) estimates."""
    rows, width = table.shape
    k = keys.shape[0]
    k_pad = _pad_to(max(k, tiling.LANE), tiling.LANE)
    block_w, w_pad = tiling.fit_block(block_w, width)
    keys_p = jnp.pad(jnp.asarray(keys, jnp.int32).reshape(1, -1),
                     ((0, 0), (0, k_pad - k)))
    table_p = jnp.pad(table, ((0, 0), (0, w_pad - width)))
    meta = jnp.array([jnp.uint32(seed).astype(jnp.int32)], jnp.int32)
    grid = (w_pad // block_w,)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, width=width, block_w=block_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, k_pad), lambda j, *_: (0, 0)),
                pl.BlockSpec((rows, block_w), lambda j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((rows, k_pad), lambda j, *_: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, k_pad), jnp.float32),
        interpret=interpret,
        name="worp_countsketch_query",
    )(meta, keys_p, table_p)
    return out[:, :k]


def countsketch_estimate(table, keys, seed, interpret: bool = True):
    """Full R.Est: median over rows (tiny; computed outside the kernel)."""
    return jnp.median(countsketch_query(table, keys, seed,
                                        interpret=interpret), axis=0)


# ---------------------------------------------------------------------------
# batched multi-stream query (SketchEngine estimate/sample plane)
# ---------------------------------------------------------------------------

_META_SEED = 0
_META_COLS = 128
_LANE_BITS = tiling.LANE.bit_length() - 1


def _batched_kernel(meta_ref, keys_ref, table_ref, out_ref, *, rows: int,
                    width: int):
    # grid = (batch_blocks, key_blocks): the (B, rows, H, 128) table block
    # depends on the batch block only, so it stays resident across the keys.
    seed = meta_ref[:, _META_SEED:_META_SEED + 1].astype(jnp.uint32)  # (B,1)
    keys = keys_ref[...].astype(jnp.uint32)                           # (B,K)
    # bucket = LANE * hi + lo: hi picks a sublane row of the (H, LANE) table
    # row, lo a lane of that row
    his = jax.lax.broadcasted_iota(jnp.int32, (1, table_ref.shape[2], 1), 1)
    los = jax.lax.broadcasted_iota(jnp.int32, (1, tiling.LANE, 1), 1)

    ests = []
    for r in range(rows):
        salt = hashing.row_salt(seed, jnp.uint32(r))          # (B, 1)
        bucket = hashing.bucket_hash(keys, salt, width)       # (B, K)
        sign = hashing.sign_hash(keys, salt)                  # (B, K)
        hi = jnp.right_shift(bucket, _LANE_BITS)[:, None, :]  # (B, 1, K)
        lo = jnp.bitwise_and(bucket, tiling.LANE - 1)[:, None, :]
        onehot = (hi == his).astype(jnp.bfloat16)             # (B, H, K)
        trow = table_ref[:, r].astype(jnp.float32)            # (B, H, LANE)
        # row hi of every key's table row, lanes on sublanes: (B, LANE, K)
        part = onehot_dot(trow, onehot, (((1,), (1,)), ((0,), (0,))))
        # lane lo of it: one nonzero term summed with zeros, so exact
        est = jnp.sum(jnp.where(lo == los, part, 0.0), axis=1,
                      keepdims=True)                           # (B, 1, K)
        ests.append(est * sign[:, None, :])
    out_ref[...] = jnp.concatenate(ests, axis=1)              # (B, rows, K)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def countsketch_query_batched(
    tables: jnp.ndarray,   # (B, rows, width) per-stream tables
    keys: jnp.ndarray,     # (B, k) per-stream key batches
    seeds: jnp.ndarray,    # (B,) per-stream hash seeds
    block_b: int = tiling.BLOCK_B,
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-row signed bucket reads for B streams in ONE pallas_call.

    Returns (B, rows, k) estimates; stream b is queried against its own
    table and seed, so independent engine streams batch without sharing
    randomness.  The batch tiles as ``tiling.batch_block``: fewer than
    ``SUBLANE`` streams need no padded tables.  The width pads with zero
    columns to whole (SUBLANE, LANE) tiles, never addressed since every
    bucket is below ``width``, and each table row is read as (H, LANE).
    """
    B, rows, width = tables.shape
    k = keys.shape[1]
    block_b, b_pad = tiling.batch_block(block_b, B)
    w_pad = _pad_to(width, tiling.SUBLANE * tiling.LANE)
    h = w_pad // tiling.LANE
    block_k, k_pad = tiling.fit_block(tiling.query_block_k(block_b, h),
                                      max(k, 1))

    keys_p = jnp.pad(jnp.asarray(keys, jnp.int32),
                     ((0, b_pad - B), (0, k_pad - k)))
    tables_p = jnp.pad(tables, ((0, b_pad - B), (0, 0), (0, w_pad - width)))
    tables_p = tables_p.reshape(b_pad, rows, h, tiling.LANE)
    seeds = jnp.broadcast_to(jnp.asarray(seeds, jnp.uint32), (B,))
    meta = jnp.zeros((b_pad, _META_COLS), jnp.int32)
    meta = meta.at[:B, _META_SEED].set(seeds.astype(jnp.int32))

    grid = (b_pad // block_b, k_pad // block_k)
    out = pl.pallas_call(
        functools.partial(_batched_kernel, rows=rows, width=width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, _META_COLS), lambda b, q: (b, 0)),
            pl.BlockSpec((block_b, block_k), lambda b, q: (b, q)),
            pl.BlockSpec((block_b, rows, h, tiling.LANE),
                         lambda b, q: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, rows, block_k),
                               lambda b, q: (b, 0, q)),
        out_shape=jax.ShapeDtypeStruct((b_pad, rows, k_pad), jnp.float32),
        # the table block, double-buffered, over the default 16 MiB of
        # scoped VMEM that holds the tiles query_block_k sizes
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * block_b * rows * w_pad * 4 + (16 << 20)),
        interpret=interpret,
        name="worp_countsketch_query_batched",
    )(meta, keys_p, tables_p)
    return out[:B, :, :k]


def countsketch_estimate_batched(tables, keys, seeds, interpret: bool = True,
                                 **kw):
    """Batched R.Est: (B, k) median-over-rows from one kernel dispatch."""
    return jnp.median(countsketch_query_batched(tables, keys, seeds,
                                                interpret=interpret, **kw),
                      axis=1)
