"""Pallas TPU kernel: K range predicates in ONE pass over packed words.

Batched variant of ``packed_filter``: a (K, 2) code-range table sits in
SMEM while the grid slides (block_rows, 128) tiles of bit-packed words
through VMEM.  Each field is shift/mask-extracted from its word exactly
once and compared against all K [lo, hi] ranges, so the dominant costs —
the HBM read of the packed column and the per-field extraction — are
paid once and amortized over K concurrent queries.  This is the
serving-side answer to the paper's single-query §4.2.2 filter: scan
traffic from many users batches into one pass over the compressed data.

Outputs are K bitmaps aligned with the packed words (bit f of
bitmap[k, i] = predicate k of the code in field f of words[i]) plus a
(K, tiles) count matrix for per-predicate selectivity estimates.

Empty ranges are encoded as lo > hi (e.g. (1, 0)): no uint32 satisfies
``v >= lo and v <= hi``, so the predicate contributes an all-zero bitmap
without any host-side special-casing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 256
LANES = 128


def _make_kernel(width: int, n_preds: int):
    per = 32 // width

    def kernel(ranges_ref, w_ref, bitmap_ref, count_ref):
        fmask = jnp.uint32((1 << width) - 1)
        w = w_ref[...]                                   # [rows, 128]
        accs = [jnp.zeros_like(w) for _ in range(n_preds)]
        cnts = [jnp.zeros((), jnp.int32) for _ in range(n_preds)]
        for f in range(per):  # static unroll: per in {1,2,4,8,16,32}
            v = (w >> jnp.uint32(f * width)) & fmask     # extracted ONCE
            for k in range(n_preds):                     # ...reused K times
                lo = ranges_ref[k, 0]
                hi = ranges_ref[k, 1]
                p = jnp.logical_and(v >= lo, v <= hi)
                accs[k] = accs[k] | (p.astype(jnp.uint32) << jnp.uint32(f))
                cnts[k] = cnts[k] + jnp.sum(p.astype(jnp.int32))
        for k in range(n_preds):
            bitmap_ref[k] = accs[k]
            count_ref[k, 0] = cnts[k]

    return kernel


@functools.partial(jax.jit, static_argnames=("width", "block_rows", "interpret"))
def multi_range_filter_packed_2d(
    words: jax.Array,       # uint32 [rows, 128]
    ranges: jax.Array,      # uint32 [K, 2] inclusive [lo, hi] per predicate
    width: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = True,
):
    rows = words.shape[0]
    n_preds = ranges.shape[0]
    assert words.shape[1] == LANES and rows % block_rows == 0, words.shape
    assert ranges.shape == (n_preds, 2), ranges.shape
    grid = (rows // block_rows,)
    ranges = jnp.asarray(ranges, jnp.uint32)
    bitmaps, counts = pl.pallas_call(
        _make_kernel(width, n_preds),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_preds, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_preds, block_rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((n_preds, 1), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_preds, rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((n_preds, grid[0]), jnp.int32),
        ],
        interpret=interpret,
    )(ranges, words)
    return bitmaps, counts
