"""Jitted public wrappers around the Pallas kernels.

Off the TPU the kernels run in ``interpret=True`` mode, which executes
the kernel body in Python -- bit-accurate for validation against the
:mod:`repro.kernels.ref` oracles.  On TPU they compile to Mosaic.
:func:`interpret` is the one place that decides which.

``attention`` / ``norm`` expose an ``impl`` switch ("pallas" | "xla") so the
model stack can pick the XLA path where cost_analysis visibility matters
(the multi-pod dry-run) and the kernel path on real hardware.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .fused_combine import combine_n, fused_combine
from .rmsnorm import rmsnorm


def interpret() -> bool:
    """True where Pallas kernels must run in interpret mode: every
    backend but the TPU, whose kernels compile to Mosaic."""
    return jax.default_backend() != "tpu"


def combine(a: jnp.ndarray, b: jnp.ndarray, *, impl: str = "pallas"):
    if impl == "xla":
        return ref.fused_combine_ref(a, b)
    if a.ndim != 1:
        raise ValueError(f"the fused_combine kernel takes flat buffers, "
                         f"got shape {a.shape}; flatten it or use "
                         f"impl='xla'")
    return fused_combine(a, b, interpret=interpret())


def combine_many(stack: jnp.ndarray, *, impl: str = "pallas"):
    if impl == "xla":
        return ref.combine_n_ref(stack)
    return combine_n(stack, interpret=interpret())


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, impl: str = "xla",
              kv_valid=None, q_positions=None, return_lse: bool = False,
              block_q: int = 128, block_k: int = 512):
    if impl == "pallas" and (return_lse or kv_valid is not None
                             or q_positions is not None):
        # traced cache lengths, explicit positions and the log-sum-exp
        # need a flash-decode kernel, which does not exist yet
        raise ValueError("the Pallas flash_attention kernel takes no "
                         "kv_valid / q_positions / return_lse; use "
                         "impl='xla'")
    if return_lse:
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       window=window, scale=scale,
                                       kv_valid=kv_valid,
                                       q_positions=q_positions,
                                       return_lse=True)
    if impl == "chunked" and q.shape[2] > 1:
        return ref.chunked_attention_ref(q, k, v, causal=causal,
                                         window=window, scale=scale,
                                         kv_valid=kv_valid,
                                         q_positions=q_positions)
    if impl in ("xla", "chunked"):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale, kv_valid=kv_valid,
                                       q_positions=q_positions)
    return flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret())


def norm(x, w, *, eps: float = 1e-6, impl: str = "xla"):
    if impl == "xla":
        return ref.rmsnorm_ref(x, w, eps=eps)
    return rmsnorm(x, w, eps=eps, interpret=interpret())
