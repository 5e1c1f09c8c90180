"""Pallas TPU kernels: batched PQ LUT scoring.

TPU adaptation of ScaNN's AVX2 LUT16 (DESIGN.md §3). Two variants:

- `pq_score_pallas`: shared code matrix — every query scores every point,
  as a one-hot MXU contraction. Codes are expanded to one-hot IN VMEM and
  contracted against the per-query LUTs; the LUT block stays
  VMEM-resident across the whole point dimension.
      score[q, i] = sum_m luts[q, m, codes[i, m]]
                  = luts[q].reshape(m*16) · onehot(codes[i]).reshape(m*16)

- `pq_score_window_pallas`: per-query candidate windows, scored from the
  packed code table in place (DESIGN.md §3.6). Query q scores only the
  slots of ITS OWN probed partitions `parts[q]`:
      score[q, j, i] = sum_m luts[q, m, codes[parts[q, j], i, m]]
  The table is the index's only device copy of the codes, and this
  module owns its layout (`code_table`): partition p is one tile-aligned
  (R, W·128) int32 block, slot r·128 + l in lane l of row r, word w of
  the slot at lanes [w·128, (w+1)·128), subspace 8w + s in nibble s of
  word w. The probed ids arrive by scalar prefetch; each block is fetched
  by its own DMA from HBM into a ring of VMEM buffers that runs ahead
  across queries, so no window of codes is ever gathered into HBM. Each
  query has its own codes, so there is no shared operand for the MXU: a
  subspace's nibble picks its LUT entry on the VPU, by a sublane gather
  from each 8-entry half of the LUT (laid down on the sublanes of every
  lane once a query) and a select on the nibble's high bit; subspaces
  add in order j = 0..m-1.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

# Block sizes: BQ queries × BN points per grid cell. m*16 is the contraction
# dim (m=16 subspaces → 256, MXU-aligned). VMEM footprint per cell:
#   luts BQ×(m·16)·4B + codes BN×m·4B + onehot BN×(m·16)·4B + out BQ×BN·4B
#   ≈ 128·256·4 + 512·16·4 + 512·256·4 + 128·512·4 ≈ 0.9 MB  « 16 MB VMEM.
DEFAULT_BQ = 128
DEFAULT_BN = 512

# Window variant: code blocks in flight. Each is one partition's
# (R, W·128) int32 block (32 KB at glove-100's 2,048 slots and m=25).
WIN_DEPTH = 8
LANES = 128      # lanes of a TPU vector register and tile
NIBBLES = 8      # 4-bit codes a word of the code table


def code_table_shape(c: int, pmax: int, m: int) -> tuple:
    """Shape of the code table of c partitions of pmax slots and m 4-bit
    subspaces: (c, R, W·128), R = ⌈pmax/1024⌉·8 slot rows (a whole number
    of (8, 128) tiles, so each partition is one aligned DMA) and W =
    ⌈m/8⌉ words a slot."""
    return (c, max(-(-pmax // (8 * LANES)), 1) * 8, -(-m // NIBBLES) * LANES)


def code_table(codes) -> np.ndarray:
    """(c, pmax, m) 4-bit PQ codes → the code table: word w of slot
    r·128 + l at [p, r, w·128 + l], subspace 8w + s in nibble s (bits
    4s..4s+3) of word w; padding slots and nibbles are 0. Two nibbles
    make a byte and four little-endian bytes a word."""
    codes = np.asarray(codes, np.uint8)
    c, pmax, m = codes.shape
    _, rows, wl = code_table_shape(c, pmax, m)
    words = wl // LANES
    nib = np.zeros((c, rows * LANES, words * NIBBLES), np.uint8)
    nib[:, :pmax, :m] = codes
    packed = (nib[..., 0::2] | nib[..., 1::2] << 4).view("<u4")
    table = packed.reshape(c, rows, LANES, words).transpose(0, 1, 3, 2)
    return np.ascontiguousarray(table).reshape(c, rows, wl).view(np.int32)


def code_table_codes(table, m: int, pmax: int):
    """Inverse of `code_table` over the last two axes: (..., R, W·128)
    table rows → (..., pmax, m) int32 codes. numpy in, numpy out; a jax
    array (the CPU search fallback) stays on the device."""
    xp = np if isinstance(table, np.ndarray) else jnp
    *lead, rows, wl = table.shape
    words = table.reshape(*lead, rows, wl // LANES, LANES)
    codes = xp.stack([(words[..., j // NIBBLES, :] >> 4 * (j % NIBBLES)) & 15
                      for j in range(m)], axis=-1)
    return codes.reshape(*lead, rows * LANES, m)[..., :pmax, :]


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None → auto-detect: compile to Mosaic on TPU, interpret elsewhere."""
    return kernels.interpret_mode() if interpret is None else interpret


def _pq_score_kernel(lut_ref, codes_ref, out_ref, *, n_centers: int):
    codes = codes_ref[...]                                   # (BN, m) int32
    onehot = (codes[:, :, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_centers), 2))
    onehot = onehot.astype(jnp.float32).reshape(codes.shape[0], -1)  # (BN, m*16)
    lut = lut_ref[...]                                       # (BQ, m*16)
    out_ref[...] = jax.lax.dot_general(
        lut, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (BQ, BN)


@functools.partial(jax.jit, static_argnames=("n_centers", "bq", "bn", "interpret"))
def pq_score_pallas(luts, codes, n_centers: int = 16,
                    bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                    interpret: Optional[bool] = None):
    """luts (nq, m, 16) f32, codes (n, m) int32 → (nq, n) f32 scores.

    interpret=None auto-detects the backend (Mosaic on TPU, interpret mode
    elsewhere) — pass an explicit bool only to force one mode.
    """
    interpret = _resolve_interpret(interpret)
    nq, m, k = luts.shape
    n = codes.shape[0]
    assert k == n_centers
    lutmat = luts.reshape(nq, m * k)
    # pad to block multiples (zero LUT rows / zero codes are harmless: stripped)
    qpad = (-nq) % bq
    npad = (-n) % bn
    lutmat = jnp.pad(lutmat, ((0, qpad), (0, 0)))
    codes_p = jnp.pad(codes.astype(jnp.int32), ((0, npad), (0, 0)))
    grid = (lutmat.shape[0] // bq, codes_p.shape[0] // bn)
    out = pl.pallas_call(
        functools.partial(_pq_score_kernel, n_centers=n_centers),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, m * k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, m), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (lutmat.shape[0], codes_p.shape[0]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(lutmat, codes_p)
    return out[:nq, :n]


def _lookup(tables, nib):
    """tables[nib] elementwise for (8, 128) nibbles in [0, 16): a sublane
    gather of the low 3 bits from each 8-entry half, then a select on
    the high bit (exact)."""
    low = nib & 7
    lo, hi = (jnp.take_along_axis(tab, low, axis=0, mode="promise_in_bounds")
              for tab in tables)
    return jnp.where(nib > 7, hi, lo)


def _pq_score_window_kernel(parts_ref, lut_ref, table_ref, out_ref, buf,
                            sem, lut_rows, *, t: int, m: int, depth: int):
    # Block b = q·t + j is partition parts[b]; it lands in ring slot
    # b % depth. Block b + depth - 1 is started as block b is waited on,
    # into the slot block b - 1 has just freed, so depth - 1 blocks are in
    # flight while one is scored; the ring runs across grid steps (the
    # grid is sequential), primed once at the first query.
    q = pl.program_id(0)
    nblk = pl.num_programs(0) * t

    def fetch(b):
        slot = b % depth
        return pltpu.make_async_copy(table_ref.at[parts_ref[b]],
                                     buf.at[slot], sem.at[slot])

    @pl.when(q == 0)
    def _():
        for b in range(depth - 1):
            @pl.when(b < nblk)
            def _():
                fetch(b).start()

    # LUT half h of subspace i, entry 8h + e, on sublane e of every lane:
    # the source of the sublane gather, built once a query
    lut = lut_ref[0]                                        # (8, 2m)
    for c in range(2 * m):
        lut_rows[c] = jnp.broadcast_to(lut[:, c:c + 1], (8, LANES))
    groups = out_ref.shape[2] // 8

    def probe(j, carry):
        b = q * t + j

        @pl.when(b + depth - 1 < nblk)
        def _():
            fetch(b + depth - 1).start()

        fetch(b).wait()
        block = buf.at[b % depth]
        acc = [jnp.zeros((8, LANES), jnp.float32) for _ in range(groups)]
        for i in range(m):
            w, s = divmod(i, NIBBLES)
            tables = lut_rows[2 * i], lut_rows[2 * i + 1]
            for r in range(groups):
                word = block[8 * r:8 * r + 8, w * LANES:(w + 1) * LANES]
                acc[r] = acc[r] + _lookup(tables, (word >> 4 * s) & 15)
        for r in range(groups):
            out_ref[0, j, 8 * r:8 * r + 8] = acc[r]
        return carry

    jax.lax.fori_loop(0, t, probe, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_score_window_pallas(luts, table, parts,
                           interpret: Optional[bool] = None):
    """luts (nq, m, 16) f32, table (c, R, W·128) int32 packed code table,
    parts (nq, t) int32 probed partitions → (nq, t, R·128) f32 scores:
    row [q, j] scores partition parts[q, j]'s R·128 slots (padding slots
    hold code 0 and score as such; the caller masks them by id).
    """
    interpret = _resolve_interpret(interpret)
    nq, m, k = luts.shape
    _, rows, wl = table.shape
    assert k == 16 and table.shape[1:] == code_table_shape(
        1, rows * LANES, m)[1:], (luts.shape, table.shape)
    t = parts.shape[1]
    depth = min(WIN_DEPTH, nq * t)
    # [q, e, 2i + h] = luts[q, i, 8h + e]: a query's halves as columns
    lut_cols = luts.reshape(nq, m, 2, 8).transpose(0, 3, 1, 2).reshape(
        nq, 8, 2 * m)
    out = pl.pallas_call(
        functools.partial(_pq_score_window_kernel, t=t, m=m, depth=depth),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nq,),
            in_specs=[
                pl.BlockSpec((1, 8, 2 * m), lambda q, p: (q, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, t, rows, LANES),
                                   lambda q, p: (q, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((depth, rows, wl), jnp.int32),
                            pltpu.SemaphoreType.DMA((depth,)),
                            pltpu.VMEM((2 * m, 8, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nq, t, rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(parts.reshape(-1).astype(jnp.int32), lut_cols, table)
    return out.reshape(nq, t, rows * LANES)
