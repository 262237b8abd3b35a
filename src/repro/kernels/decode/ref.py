"""jnp fused reference for the multi-tile batched decode — the XLA path.

This is not just the kernel oracle: on non-TPU backends it IS the batched
decode implementation (one jitted XLA dispatch per size bucket).  Every op
follows the numpy ``decode_tile`` arithmetic, so the two agree to f32
rounding accumulated over at most F frames:

- dequant + the two 8x8 IDCT matmuls use the same two-GEMM contraction
  order as ``np.einsum``, at ``HIGHEST`` precision — on a TPU the default
  f32 contraction rounds its inputs to bf16, far outside that agreement;
- the GOP reconstruction uses a *sequential* ``lax.scan`` prefix sum, the
  accumulation order of ``np.cumsum`` (``jnp.cumsum`` lowers to a
  log-depth parallel scan).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.codec.quant import quant_matrix
from repro.codec.transform import dct_matrix


def decode_fused_ref(q: jnp.ndarray, qp: int) -> jnp.ndarray:
    """q: [F, M, 8, 8] int16 (row 0 intra, rows 1+ inter) -> [F, M, 8, 8]
    f32 reconstructed frames (cumulative over F)."""
    n_frames = q.shape[0]
    d = jnp.asarray(dct_matrix())
    mk = jnp.asarray(quant_matrix(qp, True))
    mp = jnp.asarray(quant_matrix(qp, False))
    if n_frames == 1:
        scale = mk[None]
    else:
        scale = jnp.concatenate(
            [mk[None], jnp.broadcast_to(mp, (n_frames - 1, 8, 8))], axis=0)
    c = (q.astype(jnp.float32) * scale[:, None]).reshape(-1, 8, 8)
    hi = jax.lax.Precision.HIGHEST
    x = jnp.einsum("ji,njk->nik", d, c, precision=hi)
    x = jnp.einsum("nik,kl->nil", x, d, precision=hi).reshape(q.shape)
    if n_frames == 1:
        return x

    def step(carry, row):
        s = carry + row
        return s, s

    _, rest = jax.lax.scan(step, x[0], x[1:])
    return jnp.concatenate([x[:1], rest], axis=0)
