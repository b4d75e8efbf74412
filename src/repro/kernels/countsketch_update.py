"""Pallas TPU kernel: fused p-ppswor transform + CountSketch accumulation.

This is the data-plane hot spot of WORp gradient compression: one pass over
every gradient byte, hashing each coordinate into R sketch rows.

TPU adaptation (DESIGN.md Sec. 3): GPU implementations use atomicAdd scatter;
TPUs have no atomics, so the scatter is restructured as a ONE-HOT MATMUL:

    for each value block  v  (1, B)  streamed HBM -> VMEM:
        keys    = base + global offsets           (VPU iota)
        r_x     = D[hash(key)]                    (VPU, fused transform Eq. 5;
                                                   D = Exp[1] ppswor / U(0,1]
                                                   priority per static scheme)
        for each sketch row r:
            bucket_r = hash_r(key) mod W          (VPU multiply-shift)
            onehot   = (bucket_r == col_ids)      (B, WB)  in VREGs
            table[r] += (sign_r * v / r_x^{1/p}) @ onehot   (MXU)

The (rows, WB) table block stays resident in VMEM across the whole inner grid
sweep (output revisiting + @pl.when init), so HBM traffic is the input stream
plus one table write per width block -- the roofline optimum for a one-pass
sketch up to the width-block re-read factor ceil(W / WB).

Grid: (width_blocks, n_blocks), n innermost => the table block for width
block j accumulates over all n blocks before moving on.

Batched variant (``countsketch_update_batched``): the grid grows a LEADING
BATCH dimension (batch_blocks, width_blocks, n_blocks) so B independent
streams share one ``pallas_call`` instead of a Python loop of B dispatches.
Each kernel invocation processes a (block_b, block_n) tile of streams at
once -- per-stream seeds/base-keys/lengths ride in a (B, 128) meta table --
and the one-hot scatter becomes a BATCHED matmul (B contractions on the MXU,
one numpy einsum in interpret mode), amortizing dispatch + hash + iota
overhead across streams.  This is the SketchEngine data-plane fast path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing, transforms

from . import tiling
from .onehot import onehot_dot


def _kernel(meta_ref, vals_ref, table_ref, *, rows: int, width: int,
            block_n: int, block_w: int, p: float | None, scheme: str):
    j = pl.program_id(0)  # width block
    i = pl.program_id(1)  # value block

    seed = meta_ref[0].astype(jnp.uint32)
    tseed = meta_ref[1].astype(jnp.uint32)
    base = meta_ref[2].astype(jnp.uint32)
    n_valid = meta_ref[3]

    @pl.when(i == 0)
    def _init():
        table_ref[...] = jnp.zeros_like(table_ref)

    vals = vals_ref[...].astype(jnp.float32)  # (1, B)
    offs = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    valid = offs < n_valid
    keys = base + offs.astype(jnp.uint32)

    if p is not None:
        # Fused bottom-k transform (Eq. 5): v -> v / r_x^{1/p}; the scheme
        # dispatch is static, so ppswor (Exp[1]) and priority (U(0,1])
        # randomizers both trace into the kernel as pure VPU ops.
        r_x = transforms.randomizer(keys, tseed, scheme)
        vals = vals * r_x ** jnp.float32(-1.0 / p)
    vals = jnp.where(valid, vals, 0.0)

    col0 = j * block_w
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_w), 1) + col0

    contribs = []
    for r in range(rows):
        salt = hashing.row_salt(seed, jnp.uint32(r))
        bucket = hashing.bucket_hash(keys, salt, width)       # (1, B)
        sign = hashing.sign_hash(keys, salt)                  # (1, B)
        sv = (sign * vals)                                    # (1, B)
        onehot = (bucket.reshape(block_n, 1) == cols).astype(jnp.float32)
        contribs.append(onehot_dot(sv, onehot, (((1,), (0,)), ((), ()))))
    table_ref[...] += jnp.concatenate(contribs, axis=0)  # (rows, WB)


@functools.partial(
    jax.jit,
    static_argnames=("rows", "width", "p", "scheme", "block_n", "block_w",
                     "interpret"),
)
def countsketch_update(
    values: jnp.ndarray,
    rows: int,
    width: int,
    seed,
    p: float | None = None,
    scheme: str = transforms.PPSWOR,
    transform_seed=0,
    base_key=0,
    block_n: int = tiling.SINGLE_BLOCK_N,
    block_w: int = tiling.SINGLE_BLOCK_W,
    interpret: bool = True,
) -> jnp.ndarray:
    """Sketch a dense vector segment; returns the (rows, width) table.

    ``values[i]`` is the frequency of key ``base_key + i``.  With ``p`` set,
    the bottom-k transform of ``scheme`` is fused (gradient-compression hot
    path).  ``interpret=True`` runs the kernel body on CPU (this container);
    on real TPU pass ``interpret=False``.
    """
    n = values.shape[0]
    block_w, w_pad = tiling.fit_block(block_w, width)
    block_n, n_pad = tiling.fit_block(block_n, n)
    vals = jnp.pad(values.reshape(1, -1), ((0, 0), (0, n_pad - n)))
    meta = jnp.array(
        [jnp.uint32(seed).astype(jnp.int32),
         jnp.uint32(transform_seed).astype(jnp.int32),
         jnp.uint32(base_key).astype(jnp.int32),
         jnp.int32(n)],
        dtype=jnp.int32,
    )
    grid = (w_pad // block_w, n_pad // block_n)
    table = pl.pallas_call(
        functools.partial(_kernel, rows=rows, width=width, block_n=block_n,
                          block_w=block_w, p=p, scheme=scheme),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((1, block_n), lambda j, i, *_: (0, i))],
            out_specs=pl.BlockSpec((rows, block_w), lambda j, i, *_: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, w_pad), jnp.float32),
        interpret=interpret,
        name="worp_countsketch_update",
    )(meta, vals)
    return table[:, :width]


# ---------------------------------------------------------------------------
# batched multi-stream kernel (SketchEngine fast path)
# ---------------------------------------------------------------------------

# meta table layout, one row per stream (padded to a 128-lane tile) --
# SHARED with the scatter kernel (countsketch_scatter.py imports these, so
# the layout is defined exactly once):
_META_SEED, _META_TSEED, _META_BASE, _META_N = 0, 1, 2, 3
_META_COLS = 128


def _broadcast_stream_params(B, n, seeds, transform_seeds, lengths):
    """Per-stream (B,) seed/transform-seed/length vectors from scalars or
    partial inputs (the common prologue of every batched kernel wrapper)."""
    seeds = jnp.broadcast_to(jnp.asarray(seeds, jnp.uint32), (B,))
    if transform_seeds is None:
        transform_seeds = jnp.zeros((B,), jnp.uint32)
    transform_seeds = jnp.broadcast_to(
        jnp.asarray(transform_seeds, jnp.uint32), (B,))
    if lengths is None:
        lengths = jnp.full((B,), n, jnp.int32)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    return seeds, transform_seeds, lengths


def _stream_meta(b_pad, seeds, transform_seeds, lengths, base_keys=None):
    """(b_pad, _META_COLS) scalar-prefetch meta table, one row per stream;
    padded streams keep length 0 and contribute nothing."""
    B = seeds.shape[0]
    meta = jnp.zeros((b_pad, _META_COLS), jnp.int32)
    meta = meta.at[:B, _META_SEED].set(seeds.astype(jnp.int32))
    meta = meta.at[:B, _META_TSEED].set(transform_seeds.astype(jnp.int32))
    if base_keys is not None:
        meta = meta.at[:B, _META_BASE].set(base_keys.astype(jnp.int32))
    return meta.at[:B, _META_N].set(lengths)


def _batched_kernel(meta_ref, vals_ref, table_ref, *, rows: int, width: int,
                    block_n: int, block_w: int, p: float | None, scheme: str):
    # grid = (batch_blocks, width_blocks, n_blocks); n innermost so each
    # (stream-block, width-block) table tile accumulates over the stream.
    j = pl.program_id(1)  # width block
    i = pl.program_id(2)  # value block

    @pl.when(i == 0)
    def _init():
        table_ref[...] = jnp.zeros_like(table_ref)

    seed = meta_ref[:, _META_SEED:_META_SEED + 1].astype(jnp.uint32)   # (B,1)
    tseed = meta_ref[:, _META_TSEED:_META_TSEED + 1].astype(jnp.uint32)
    base = meta_ref[:, _META_BASE:_META_BASE + 1].astype(jnp.uint32)
    n_valid = meta_ref[:, _META_N:_META_N + 1]                         # (B,1)

    vals = vals_ref[...].astype(jnp.float32)  # (B, N)
    offs = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n), 1)           # (1, N)
    valid = offs < n_valid                    # (B, N) -- ragged streams
    keys = base + offs.astype(jnp.uint32)     # (B, N) per-stream key spaces

    if p is not None:
        # per-stream transform seeds; scheme dispatch is static (see _kernel)
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
def countsketch_update_batched(
    values: jnp.ndarray,
    rows: int,
    width: int,
    seeds: jnp.ndarray,
    p: float | None = None,
    scheme: str = transforms.PPSWOR,
    transform_seeds=None,
    base_keys=None,
    lengths=None,
    block_n: int = tiling.BLOCK_N,
    block_w: int = tiling.BLOCK_W,
    block_b: int = tiling.BLOCK_B,
    interpret: bool = True,
) -> jnp.ndarray:
    """Sketch B dense vector segments in ONE pallas_call; (B, rows, width).

    ``values`` is (B, n): stream b holds the frequencies of keys
    ``base_keys[b] + [0, lengths[b])``; columns past ``lengths[b]`` are
    ignored, so ragged streams (e.g. model layers of different sizes) batch
    together.  ``seeds``/``transform_seeds`` are per-stream (B,) so streams
    stay statistically independent unless deliberately seeded equal.  The
    batch tiles as ``tiling.batch_block``: fewer than ``SUBLANE`` streams
    are one block of exactly B rows.
    """
    B, n = values.shape
    seeds, transform_seeds, lengths = _broadcast_stream_params(
        B, n, seeds, transform_seeds, lengths)
    if base_keys is None:
        base_keys = jnp.zeros((B,), jnp.uint32)
    base_keys = jnp.broadcast_to(jnp.asarray(base_keys, jnp.uint32), (B,))

    block_w, w_pad = tiling.fit_block(block_w, width)
    block_n, n_pad = tiling.fit_block(block_n, n)
    block_b, b_pad = tiling.batch_block(block_b, B)

    vals = jnp.pad(values, ((0, b_pad - B), (0, n_pad - n)))
    meta = _stream_meta(b_pad, seeds, transform_seeds, lengths,
                        base_keys=base_keys)

    grid = (b_pad // block_b, w_pad // block_w, n_pad // block_n)
    table = pl.pallas_call(
        functools.partial(_batched_kernel, rows=rows, width=width,
                          block_n=block_n, block_w=block_w, p=p,
                          scheme=scheme),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, _META_COLS), lambda b, j, i: (b, 0)),
            pl.BlockSpec((block_b, block_n), lambda b, j, i: (b, i)),
        ],
        out_specs=pl.BlockSpec((block_b, rows, block_w),
                               lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, rows, w_pad), jnp.float32),
        interpret=interpret,
        name="worp_countsketch_update_batched",
    )(meta, vals)
    return table[:B, :, :width]
