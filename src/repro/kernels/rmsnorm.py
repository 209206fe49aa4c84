"""Pallas TPU kernel: fused RMSNorm.

Fuses the square-mean reduction, rsqrt and the weight multiply into one
VMEM pass over the (rows, d_model) activations -- the unfused XLA version
reads the activation twice (once for the variance, once for the scale).
Rows are tiled in blocks; d_model stays resident in VMEM per row block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Bytes of VMEM one grid step may hold: the 16 MiB Mosaic grants a kernel
# by default on v5e, less headroom for the weight row and spills.
_VMEM_BUDGET = 12 * 1024 * 1024


def block_rows_for(d: int, dtype) -> int:
    """Rows per block that fit ``_VMEM_BUDGET`` at row width ``d``.

    A row costs its input and output tiles, each double-buffered, plus
    the two fp32 temporaries of the kernel body (``x`` and the scaled
    product).  The result is a multiple of 8 (the sublane tiling) in
    [8, 256]: 128 rows for fp32 and 192 for bf16 at d = 4096.
    """
    per_row = d * (4 * jnp.dtype(dtype).itemsize + 2 * 4)
    return max(8, min(256, _VMEM_BUDGET // per_row // 8 * 8))


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
            block_rows: Optional[int] = None,
            interpret: bool = False) -> jnp.ndarray:
    """x: (..., d); w: (d,).  Normalizes the last axis.  ``block_rows``
    defaults to :func:`block_rows_for` the row width and dtype."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    if block_rows is None:
        block_rows = block_rows_for(d, x.dtype)
    block_rows = min(block_rows, max(n, 1))
    pad = (-n) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = xf.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, w.reshape(1, d))
    return out[:n].reshape(orig_shape)
