"""Compaction-time code remap (Algorithm 1 line 9).

After ``OPD.merge_subset_flat`` rebuilds an output SCT's dictionary, every
surviving entry must be rewritten from its *old* code to its position in
the new dictionary.  The rewrite is a pure table gather: with the
per-source remap tables concatenated into one flat ``old -> new`` array
and a per-source base-offset vector, entry i maps as

    ev'[i] = flat[ ev[i] + offset[src[i]] ]        (ev < 0 stays dead)

The gather is an XLA gather (Mosaic cannot gather from a VMEM table);
the remapped int32 codes feed straight into the jitted program that
consumes them:

* ``remap_codes`` — plain remap: int32 codes in, int32 codes out, dead
  entries (-1 sources: tombstones / dropped) preserved as -1.
* ``remap_pack_codes_3d`` — the ``jax_packed`` backend: remap, then
  k-bit pack with the ``bitpack.pack_codes_3d`` Pallas kernel, in one
  jitted program, so the output column leaves the device bit-packed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bitpack as _bitpack

DEFAULT_BLOCK_ROWS = 128


@jax.jit
def remap_codes(
    evs: jax.Array,      # int32 [...]; -1 = dead entry
    srcs: jax.Array,     # int32 [...]; source SCT id per entry
    table: jax.Array,    # int32 [T]; flat remap, -1 at unused codes
    offsets: jax.Array,  # int32 [n_src]; base offset of source i in table
):
    live = evs >= 0
    idx = jnp.where(live, evs + offsets[srcs], 0)
    return jnp.where(live, table[idx], -1)


@functools.partial(jax.jit, static_argnames=("width", "block_rows", "interpret"))
def remap_pack_codes_3d(
    evs: jax.Array,      # int32 [M, per, 128]; -1 = dead entry
    srcs: jax.Array,     # int32 [M, per, 128]
    table: jax.Array,    # int32 [T]
    offsets: jax.Array,  # int32 [n_src]
    width: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = True,
):
    # dead entries and unused-code lookups (table holds -1 there) pack as
    # 0 — bit-identical to the numpy path's bitpack(clip(evs, 0));
    # padding rows enter as ev == -1.
    codes = jnp.maximum(remap_codes(evs, srcs, table, offsets), 0)
    return _bitpack.pack_codes_3d(codes, width, block_rows=block_rows,
                                  interpret=interpret)
