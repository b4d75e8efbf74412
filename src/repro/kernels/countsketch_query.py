"""Pallas TPU kernel: CountSketch query (per-row estimates for a key batch).

Estimating k keys needs table[r, bucket_r(key)] for every row r -- a gather on
GPU.  TPU adaptation: the gather becomes a one-hot matmul over width blocks:

    est_r  =  sum_j  onehot_j(keys) @ table[r, j*WB:(j+1)*WB]^T

Each (K,) accumulator tile stays in VMEM across the width sweep; the batched
variant tiles the keys too, so its one-hot block fits the chip's VMEM.
The final median-over-rows is O(R*K) and runs outside the kernel (ops layer).

Batched variant (``countsketch_query_batched``): the grid grows a leading
batch dimension so the B streams of a ``SketchEngine`` -- each with its own
table and hash seed -- are estimated by ONE ``pallas_call`` instead of a
Python loop of B dispatches.  Per-stream seeds ride in a (B, 128) meta table
and the one-hot gather becomes a batched contraction on the MXU.  This is
the engine's batched estimate / sample / candidate-refresh query plane.
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


def _batched_kernel(meta_ref, keys_ref, table_ref, out_ref, *, rows: int,
                    width: int, block_w: int):
    # grid = (batch_blocks, key_blocks, width_blocks): each (stream-block,
    # key-tile) accumulator revisits across the width sweep.
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seed = meta_ref[:, _META_SEED:_META_SEED + 1].astype(jnp.uint32)  # (B,1)
    keys = keys_ref[...].astype(jnp.uint32)                           # (B,K)
    col0 = j * block_w
    # transposed one-hot (B, WB, K): the table row is the (B, 1, WB) lhs, so
    # every operand keeps its lane dimension and Mosaic needs no gather
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, block_w, 1), 1) + col0

    ests = []
    for r in range(rows):
        salt = hashing.row_salt(seed, jnp.uint32(r))          # (B, 1)
        bucket = hashing.bucket_hash(keys, salt, width)       # (B, K)
        sign = hashing.sign_hash(keys, salt)                  # (B, K)
        onehot = (bucket[:, None, :] == cols).astype(jnp.float32)
        trow = table_ref[:, r:r + 1, :].astype(jnp.float32)   # (B, 1, WB)
        # batched contraction: B streams on the MXU -> (B, 1, K)
        part = onehot_dot(trow, onehot, (((2,), (1,)), ((0,), (0,))))
        ests.append(part * sign[:, None, :])
    out_ref[...] += jnp.concatenate(ests, axis=1)             # (B, rows, K)


@functools.partial(
    jax.jit, static_argnames=("block_w", "block_b", "interpret")
)
def countsketch_query_batched(
    tables: jnp.ndarray,   # (B, rows, width) per-stream tables
    keys: jnp.ndarray,     # (B, k) per-stream key batches
    seeds: jnp.ndarray,    # (B,) per-stream hash seeds
    block_w: int = tiling.BLOCK_W,
    block_b: int = tiling.BLOCK_B,
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-row signed bucket reads for B streams in ONE pallas_call.

    Returns (B, rows, k) estimates; stream b is queried against its own
    table and seed, so independent engine streams batch without sharing
    randomness.  The batch tiles as ``tiling.batch_block``: fewer than
    ``SUBLANE`` streams need no padded tables.
    """
    B, rows, width = tables.shape
    k = keys.shape[1]
    block_k, k_pad = tiling.fit_block(tiling.BLOCK_K, max(k, 1))
    block_w, w_pad = tiling.fit_block(block_w, width)
    block_b, b_pad = tiling.batch_block(block_b, B)

    keys_p = jnp.pad(jnp.asarray(keys, jnp.int32),
                     ((0, b_pad - B), (0, k_pad - k)))
    tables_p = jnp.pad(tables, ((0, b_pad - B), (0, 0), (0, w_pad - width)))
    seeds = jnp.broadcast_to(jnp.asarray(seeds, jnp.uint32), (B,))
    meta = jnp.zeros((b_pad, _META_COLS), jnp.int32)
    meta = meta.at[:B, _META_SEED].set(seeds.astype(jnp.int32))

    grid = (b_pad // block_b, k_pad // block_k, w_pad // block_w)
    out = pl.pallas_call(
        functools.partial(_batched_kernel, rows=rows, width=width,
                          block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, _META_COLS), lambda b, q, j: (b, 0)),
            pl.BlockSpec((block_b, block_k), lambda b, q, j: (b, q)),
            pl.BlockSpec((block_b, rows, block_w), lambda b, q, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, rows, block_k),
                               lambda b, q, j: (b, 0, q)),
        out_shape=jax.ShapeDtypeStruct((b_pad, rows, k_pad), jnp.float32),
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
