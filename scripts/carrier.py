"""Leaf-partitioned carrier: the TPU redesign of DataPartition.

The reference groups row INDICES contiguously by leaf and gathers
feature bytes through them (src/treelearner/data_partition.hpp:109-161)
— free on a cache-hierarchy CPU, dead on TPU (XLA row gather measured
36 GB/s vs a 534 GB/s stream, docs/ROOFLINE.md r5).  Instead the
per-row DATA physically rides the partition: everything a tree round
touches lives in one int8 "carrier" laid out as (T, R, 128) — T
128-column tiles of R byte-rows per column — and splitting a leaf
streams its tiles once, routing each column and compacting left/right
children into fresh tile-aligned spans (ops/partition_kernel.py).
Histogram passes then stream ONLY the frontier leaves' spans: per-pass
cost becomes proportional to the split leaves' sizes (Σ≈8N per tree,
Σ smaller-child ≈3N) instead of rounds × N.

Column byte-rows (R = 64):
  0..G-1      packed group bins (uint8 bytes)
  G..G+2      quantized weights: grad_q, hess_q (int8), cnt (0/1)
  G+3, G+4    leaf id, little-endian int16 (lo byte, SIGN-carrying hi
              byte: -1 == dead column — alloc padding / tile slack)
  G+5..G+8    perm: original row index, int32 LE (bagging hash seed,
              debugging)
  G+9..G+12   score, f32 bits LE
  G+13..G+16  label, f32 bits LE
  G+17..G+20  sample weight, f32 bits LE (ones when unweighted)

Order-free training state: scores/labels/weights permute WITH the data
so gradients, metrics and score updates are computed in "current
order" — nothing ever needs the original row order back (objectives
and metrics are row-order-invariant reductions; bagging re-derives
masks from the carried perm row).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

TILE = 128
CARRIER_ROWS = 64


def carrier_row_map(num_groups: int) -> dict:
    g = num_groups
    if g + 21 > CARRIER_ROWS:
        raise ValueError(
            f"carrier supports at most {CARRIER_ROWS - 21} feature "
            f"groups, got {g}")
    return dict(bins=0, wq=g, leaf_lo=g + 3, leaf_hi=g + 4, perm=g + 5,
                score=g + 9, label=g + 13, weight=g + 17)


def _f32_rows(x: jax.Array) -> jax.Array:
    """(N,) f32 -> (4, N) int8 little-endian byte rows (bit-exact)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.stack([(bits >> (8 * i)).astype(jnp.int8)
                      for i in range(4)])


def _i32_rows(x: jax.Array) -> jax.Array:
    return jnp.stack([(x >> (8 * i)).astype(jnp.int8) for i in range(4)])


def rows_to_f32(rows: jax.Array) -> jax.Array:
    """(4, N) int8 byte rows -> (N,) f32 (inverse of _f32_rows)."""
    b = [rows[i].astype(jnp.int32) & 255 for i in range(4)]
    bits = b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def rows_to_i32(rows: jax.Array) -> jax.Array:
    b = [rows[i].astype(jnp.int32) & 255 for i in range(4)]
    return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)


def rows_to_leaf(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """lo/hi int8 rows -> int32 leaf ids (hi carries the sign)."""
    return (lo.astype(jnp.int32) & 255) | (hi.astype(jnp.int32) << 8)


def leaf_to_rows(leaf: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return leaf.astype(jnp.int8), (leaf >> 8).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("num_tiles", "num_groups"))
def assemble_carrier(bins: jax.Array, score: jax.Array, label: jax.Array,
                     weight: jax.Array, *, num_tiles: int,
                     num_groups: int) -> jax.Array:
    """Build the canonical (T, R, 128) carrier from original-order
    arrays.  ``bins`` is (N, G) uint8; N-padded/cap-padded columns are
    dead (leaf = -1).  wq rows start zeroed (filled per tree)."""
    n = bins.shape[0]
    ncap = num_tiles * TILE
    rm = carrier_row_map(num_groups)
    rows = jnp.zeros((CARRIER_ROWS, ncap), jnp.int8)

    def put(r, arr):
        return jax.lax.dynamic_update_slice(rows, arr, (r, 0))

    pad = ncap - n
    binsT = jnp.pad(bins.astype(jnp.int8).T, ((0, 0), (0, pad)))
    rows = jax.lax.dynamic_update_slice(rows, binsT, (rm["bins"], 0))
    leaf = jnp.concatenate([jnp.zeros(n, jnp.int32),
                            jnp.full(pad, -1, jnp.int32)])
    lo, hi = leaf_to_rows(leaf)
    rows = put(rm["leaf_lo"], lo[None, :])
    rows = put(rm["leaf_hi"], hi[None, :])
    rows = put(rm["perm"], _i32_rows(
        jnp.pad(jnp.arange(n, dtype=jnp.int32), (0, pad))))
    rows = put(rm["score"], _f32_rows(jnp.pad(score, (0, pad))))
    rows = put(rm["label"], _f32_rows(jnp.pad(label, (0, pad))))
    rows = put(rm["weight"], _f32_rows(jnp.pad(weight, (0, pad))))
    return rows.reshape(CARRIER_ROWS, num_tiles, TILE).transpose(1, 0, 2)


def carrier_get_row(carrier: jax.Array, row: int,
                    count: int = 4) -> jax.Array:
    """(T, R, 128) carrier -> (count, T*128) int8 row view."""
    t = carrier.shape[0]
    sl = jax.lax.dynamic_slice_in_dim(carrier, row, count, axis=1)
    return sl.transpose(1, 0, 2).reshape(count, t * TILE)


def carrier_set_rows(carrier: jax.Array, row: int,
                     rows: jax.Array) -> jax.Array:
    """Write (k, T*128) int8 rows back into the carrier."""
    t = carrier.shape[0]
    k = rows.shape[0]
    blk = rows.reshape(k, t, TILE).transpose(1, 0, 2)
    return jax.lax.dynamic_update_slice(carrier, blk, (0, row, 0))
