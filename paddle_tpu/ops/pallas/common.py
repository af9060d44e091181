"""What the Pallas kernels share: per-shard execution under a device
mesh, the MXU matmul, and how to find a kernel in a compiled program.

GSPMD cannot partition a Mosaic kernel: lowering a ``pallas_call`` inside
a ``jax.jit`` that spans several devices is refused ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map"). Both kernels here are independent per head (and flash per
batch row), so the engine that traces a step over a mesh declares, for
the duration of that trace, the mesh and which of its axes shard the head
and batch dims; the kernel entry points then run under ``jax.shard_map``
over exactly those axes. With no declaration (one device) nothing is
wrapped.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

__all__ = ["kernel_mesh", "declared", "mxu_dot", "kernel_calls"]

_tls = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, *, heads=None, batch=None):
    """Declare, while tracing, that attention operands are sharded over
    ``mesh``: the head dim over axis ``heads`` and the batch dim over
    axis (or tuple of axes) ``batch``; ``None`` = not sharded."""
    prev = declared()
    _tls.decl = (mesh, heads, batch)
    try:
        yield
    finally:
        _tls.decl = prev


def declared():
    """``(mesh, heads_axis, batch_axes)`` of the enclosing
    :func:`kernel_mesh`, or None."""
    return getattr(_tls, "decl", None)


def mxu_dot(a, b, dims, preferred_element_type=jnp.float32):
    """``lax.dot_general`` for kernel bodies. Mosaic refuses a precision
    above DEFAULT on operands narrower than float32 ("Bad lhs type"), so
    an ambient ``jax_default_matmul_precision`` reaches float32 operands
    only."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=preferred_element_type,
        precision=(None if a.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))


def kernel_calls(compiled_text: str, name: str) -> int:
    """How many Mosaic custom calls of the Pallas kernel ``name`` (its
    ``pallas_call(name=...)``) a compiled program's text holds — the
    proof, from the program itself, that the kernel is in it."""
    return sum(1 for line in compiled_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and name in line)
