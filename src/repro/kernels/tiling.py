"""Shared grid-tiling / padding arithmetic for the WORp Pallas kernels.

Every kernel wrapper needs the same prologue: clamp the requested block
size to the (tile-padded) dimension, then pad the dimension to a whole
number of blocks.  Before this module each wrapper carried its own
``_pad_to`` copy (dense update, query, transform) and the block defaults
lived in per-function signatures; the host-side packing layer
(``repro.data.ingest_pipeline``) needs the SAME arithmetic to emit
fixed-shape blocks that feed the scatter grid without recompilation.  So
the selection logic is defined exactly once here and re-exported through
``kernels.ops`` for host-side callers.

TPU register tiling: the lane (minor) dimension of a vector register is
128 wide and the sublane dimension 8 deep -- block dimensions that map to
lanes pad to ``LANE``.  The batch of the batched kernels pads to
``SUBLANE`` only from ``SUBLANE`` streams up: a smaller batch is one block
of exactly B rows (``batch_block``), since a block dimension equal to the
whole array dimension needs no sublane padding.
"""
from __future__ import annotations

LANE = 128
SUBLANE = 8

# canonical block defaults of the batched (batch, width, n) kernel grids --
# the scatter/update data plane and the query plane share these.
BLOCK_N = 512
BLOCK_W = 1024
BLOCK_B = 8
# key tiles of the batched query (``query_block_k``): each grid step holds a
# (block_b, h, block_k) one-hot over the table's sublane rows and three
# (block_b, LANE, block_k) f32 reads.  Capping the one-hot keeps them near
# 7 MiB of a v5e's VMEM beside the resident table block, and capping the
# keys keeps a one-stream kernel's unrolled body, and its compile, small.
BLOCK_K = 1024
QUERY_ONEHOT = 1 << 20

# single-stream kernels have no batch dimension competing for VMEM, so they
# afford larger tiles.
SINGLE_BLOCK_N = 1024
SINGLE_BLOCK_W = 2048
# the standalone transform is elementwise (no table resident in VMEM).
TRANSFORM_BLOCK_N = 4096


def pad_to(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return ((x + m - 1) // m) * m


def fit_block(block: int, dim: int, tile: int = LANE) -> tuple:
    """The universal kernel-wrapper prologue: clamp ``block`` to the
    tile-padded ``dim`` and pad ``dim`` to a whole number of blocks.
    Returns ``(block, dim_pad)`` with ``dim_pad % block == 0``."""
    block = min(block, pad_to(dim, tile))
    return block, pad_to(dim, block)


def batch_block(block_b: int, B: int) -> tuple:
    """The batched kernels' batch tiling, ``(block_b, b_pad)``: one block of
    all B streams when ``B < SUBLANE`` (no padded streams), else
    ``block_b`` clamped and B padded as ``fit_block`` does on sublanes."""
    if B < SUBLANE:
        return B, B
    return fit_block(block_b, B, tile=SUBLANE)


def query_block_k(block_b: int, h: int) -> int:
    """Key tile of the batched query for ``block_b`` streams whose table
    rows are read as (h, LANE): at most ``BLOCK_K`` keys a stream and
    ``QUERY_ONEHOT`` one-hot elements a grid step, in whole lanes."""
    k = min(BLOCK_K, QUERY_ONEHOT // (block_b * h))
    return max(LANE, k // LANE * LANE)


def scatter_tiles(B: int, n: int, block_b: int = BLOCK_B,
                  block_n: int = BLOCK_N) -> tuple:
    """The batched scatter kernel's (rows, columns) tiling of a (B, n)
    batch: ``((block_b, b_pad), (block_n, n_pad))``.  ``b_pad * n_pad`` is
    the number of slots the kernel sweeps, padding included."""
    return batch_block(block_b, B), fit_block(block_n, n)


def packed_span(n: int, block_n: int = BLOCK_N, tile: int = LANE) -> int:
    """Element capacity of a fixed-shape host block covering ``n`` events
    with zero kernel-side re-padding: the returned span is already a whole
    number of (clamped) n-blocks, so a batcher that always emits this shape
    hits ONE kernel trace for the whole stream."""
    _, n_pad = fit_block(block_n, max(int(n), 1), tile)
    return n_pad
