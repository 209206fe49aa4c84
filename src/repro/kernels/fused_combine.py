"""Pallas TPU kernel: fused combine for the allreduce reduction step.

The gamma term of the paper's cost model is the per-byte combine speed.  On
TPU the combine (y = a + b over a large contiguous buffer, with fp32
accumulation for bf16 gradients) is HBM-bandwidth bound: 3 bytes moved per
combined byte.  The kernel tiles the flat buffer through VMEM in blocks
sized for double-buffered HBM->VMEM DMA, and fuses the dtype widening /
narrowing into the same pass so no extra fp32 copy of the buffer ever
exists in HBM -- that widening is exactly what a naive
``(a.astype(f32) + b.astype(f32)).astype(bf16)`` materializes.

``combine_n`` fuses K-way combines (latency-optimal schedule steps
combine several arrivals per output row) into one pass over HBM:
(K+1)/3x less traffic than K-1 chained pairwise ops.

The combine is any of the elementwise monoid kinds the schedule family
supports (``op`` = "add" | "max" | "min"): max/min cost the same one
VPU instruction per element as the add and reuse the identical VMEM
tiling -- the kernel is memory-bound either way, which is exactly why
the cost model prices all three with the same gamma.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# 8 MiB fp32 working set per block-pair fits comfortably in 16 MiB VMEM
# with double buffering; lane dim must be a multiple of 128.
_BLOCK = 128 * 1024  # elements per tile (flat layout, reshaped to (rows,128))
_LANES = 128

_OPS = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


def _combine_kernel(a_ref, b_ref, o_ref, *, accum_dtype, op):
    a = a_ref[...].astype(accum_dtype)
    b = b_ref[...].astype(accum_dtype)
    o_ref[...] = _OPS[op](a, b).astype(o_ref.dtype)


def _combine_n_kernel(s_ref, o_ref, *, accum_dtype, k, op):
    acc = s_ref[0].astype(accum_dtype)
    for i in range(1, k):
        acc = _OPS[op](acc, s_ref[i].astype(accum_dtype))
    o_ref[...] = acc.astype(o_ref.dtype)


def _pad_flat(x, block):
    n = x.shape[-1]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, n


@functools.partial(jax.jit, static_argnames=("accum_dtype", "interpret",
                                             "block", "op"))
def fused_combine(a: jnp.ndarray, b: jnp.ndarray, *,
                  accum_dtype=jnp.float32, interpret: bool = False,
                  block: int = _BLOCK, op: str = "add") -> jnp.ndarray:
    """y = a (op) b elementwise over flat buffers, fp32 accumulation."""
    assert a.shape == b.shape and a.ndim == 1, (a.shape, b.shape)
    af, n = _pad_flat(a, block)
    bf, _ = _pad_flat(b, block)
    rows = block // _LANES
    grid = af.shape[0] // block
    a2 = af.reshape(grid * rows, _LANES)
    b2 = bf.reshape(grid * rows, _LANES)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, accum_dtype=accum_dtype, op=op),
        grid=(grid,),
        in_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a2.shape, a.dtype,
                                       vma=jax.typeof(a2).vma),
        interpret=interpret,
    )(a2, b2)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("accum_dtype", "interpret",
                                             "block", "op"))
def combine_n(stack: jnp.ndarray, *, accum_dtype=jnp.float32,
              interpret: bool = False, block: int = _BLOCK,
              op: str = "add") -> jnp.ndarray:
    """Reduce K rows (K, n) -> (n,) by ``op`` in a single HBM pass."""
    assert stack.ndim == 2
    k = stack.shape[0]
    sf, n = _pad_flat(stack, block)
    rows = block // _LANES
    grid = sf.shape[-1] // block
    s2 = sf.reshape(k, grid * rows, _LANES)
    out = pl.pallas_call(
        functools.partial(_combine_n_kernel, accum_dtype=accum_dtype, k=k,
                          op=op),
        grid=(grid,),
        in_specs=[pl.BlockSpec((k, rows, _LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(s2.shape[1:], stack.dtype,
                                       vma=jax.typeof(s2).vma),
        interpret=interpret,
    )(s2)
    return out.reshape(-1)[:n]
