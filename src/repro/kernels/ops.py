"""Dispatch layer over the Pallas kernels and their pure-jnp references.

The caller names what runs; nothing here looks at the backend:

* ``impl="kernel"`` (default) — the compiled Pallas kernel.  It compiles
  for a TPU only; anywhere else the call fails, never falling back quietly.
* ``impl="interpret"`` — the same kernel body in the Pallas interpreter
  (any backend; how the CPU tests run it).
* ``impl="ref"`` — the oracle in :mod:`repro.kernels.ref`.

No model calls these yet: the serving path's attention and SSD are the
plain-JAX lowerings in :mod:`repro.models.layers`.  Operands use the
kernels' head-major layouts.
"""

from __future__ import annotations

from . import ref
from .flash_attention import flash_attention as _flash_kernel
from .paged_attention import paged_attention as _paged_kernel
from .ssd_scan import ssd_scan as _ssd_kernel

IMPLS = ("kernel", "interpret", "ref")


def _checked(impl: str) -> str:
    if impl not in IMPLS:
        raise ValueError(f"impl={impl!r}: choose from {IMPLS}")
    return impl


def flash_attention(q, k, v, *, causal=True, window=None,
                    softmax_scale=None, impl: str = "kernel"):
    if _checked(impl) == "ref":
        return ref.flash_attention_ref(
            q, k, v, causal=causal, window=window,
            softmax_scale=softmax_scale)
    return _flash_kernel(
        q, k, v, causal=causal, window=window, softmax_scale=softmax_scale,
        interpret=impl == "interpret")


def paged_attention(q, k_pages, v_pages, block_tables, context_lens, *,
                    softmax_scale=None, impl: str = "kernel"):
    if _checked(impl) == "ref":
        return ref.paged_attention_ref(
            q, k_pages, v_pages, block_tables, context_lens,
            softmax_scale=softmax_scale)
    return _paged_kernel(
        q, k_pages, v_pages, block_tables, context_lens,
        softmax_scale=softmax_scale, interpret=impl == "interpret")


def ssd_scan(xdt, dA, Bm, Cm, *, chunk: int = 128, impl: str = "kernel"):
    if _checked(impl) == "ref":
        return ref.ssd_scan_ref(xdt, dA, Bm, Cm)
    return _ssd_kernel(xdt, dA, Bm, Cm, chunk=chunk,
                       interpret=impl == "interpret")
