"""Pallas TPU kernel: multi-tile fused dequant + 8x8 IDCT + GOP cumsum.

One dispatch decodes a whole scheduler batch: the input is a flat *block
stream* ``[F, M, 8, 8]`` where ``F`` is the (bucketed) frames-per-GOP depth
and each of the ``M`` columns is one 8x8 block of one ``(tile, GOP,
block-mask)`` selection — ROI block-gather happens on the host while
assembling the stream, so masked-out blocks never reach the kernel.

Row 0 holds intra-coded keyframe coefficients, rows 1..F-1 the inter-coded
P-frame residuals; the closed-loop reconstruction ``out[f] = out[f-1] +
IDCT(dequant(q[f]))`` is the sequential sum the numpy oracle computes.  The
matmuls run at ``HIGHEST`` precision (full f32 on the MXU), so the result
matches per-tile ``decode_tile`` to f32 rounding accumulated over at most F
frames — not bit-for-bit, since the MXU sums in its own order (padding rows
with zero coefficients only ever *appends* frames, which callers slice off).

Grid is over column blocks: each program reconstructs ``[F, blk, 8, 8]``
with F statically unrolled — two MXU matmuls + a VPU scale per frame.  The
block width comes from :func:`block_columns`: Mosaic pads each trailing
8x8 slab to a whole (sublane, 128-lane) tile, so the VMEM a program needs
grows with ``F * blk`` and a fixed ``blk`` stops compiling at full GOPs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.codec.quant import quant_matrix
from repro.codec.transform import dct_matrix

#: widest column block per program
BLK = 128
#: VMEM bytes one (frame, column) slab costs in a program: the int16 input
#: pads 8x8 to a (16, 128) tile and the f32 output to an (8, 128) tile,
#: 4 KiB each, and the pipeline double-buffers both
SLAB_VMEM_BYTES = 2 * (16 * 128 * 2 + 8 * 128 * 4)
#: default scoped VMEM of a TPU v5e core; the blocks must fit in it
VMEM_BUDGET_BYTES = 16 << 20


def block_columns(n_frames: int, m: int) -> int:
    """Columns per program for an ``[n_frames, m, 8, 8]`` stream: the widest
    power of two <= ``BLK`` whose padded, double-buffered ``[F, blk, 8, 8]``
    in+out blocks fit :data:`VMEM_BUDGET_BYTES` (F=16 -> 64, F=32 -> 32),
    capped at ``m`` (a power of two, so ``m % blk == 0``)."""
    fit = max(1, VMEM_BUDGET_BYTES // (SLAB_VMEM_BYTES * n_frames))
    return min(BLK, 1 << (fit.bit_length() - 1), m)


def _kernel(q_ref, d_ref, mk_ref, mp_ref, out_ref):
    d = d_ref[...]
    hi = jax.lax.Precision.HIGHEST
    n_frames = q_ref.shape[0]
    acc = None
    for f in range(n_frames):            # static unroll over the GOP depth
        m = mk_ref[...] if f == 0 else mp_ref[...]
        c = q_ref[f].astype(jnp.float32) * m      # dequant (VPU)
        x = jnp.einsum("ji,njk->nik", d, c, precision=hi)   # D^T @ C (MXU)
        x = jnp.einsum("nik,kl->nil", x, d, precision=hi)   # ...  @ D (MXU)
        acc = x if acc is None else acc + x       # closed-loop cumsum
        out_ref[f] = acc


def decode_gop_blocks(q: jnp.ndarray, qp: int, *,
                      interpret: bool = False) -> jnp.ndarray:
    """q: [F, M, 8, 8] int16 -> reconstructed [F, M, 8, 8] f32, in column
    blocks of :func:`block_columns` (``M`` a power of two, or a multiple
    of the block)."""
    n_frames, m = q.shape[:2]
    blk = block_columns(n_frames, m)
    if m % blk:
        raise ValueError(f"{m} columns do not split into blocks of {blk}")
    return pl.pallas_call(
        _kernel,
        grid=(m // blk,),
        in_specs=[
            pl.BlockSpec((n_frames, blk, 8, 8), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((8, 8), lambda i: (0, 0)),
            pl.BlockSpec((8, 8), lambda i: (0, 0)),
            pl.BlockSpec((8, 8), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_frames, blk, 8, 8), lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_frames, m, 8, 8), jnp.float32),
        interpret=interpret,
    )(q, jnp.asarray(dct_matrix()), jnp.asarray(quant_matrix(qp, True)),
      jnp.asarray(quant_matrix(qp, False)))
