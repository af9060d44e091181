"""Flash attention entry point.

Reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu (CUDA
flash-attn). TPU-native: a Pallas blockwise-softmax kernel
(ops/pallas/flash_attention.py).

Which implementation runs is a stated rule, never the outcome of a
caught error. ``impl=None``: the compiled Pallas kernel when the
backend is a TPU, there is no dropout and both sequence lengths tile
(``flash_attention.tileable``); the XLA SDPA emitter otherwise. A
caller may name the implementation: ``"pallas"`` (compiled — lowering
it for a CPU raises), ``"interpret"`` (the same kernel through the
Pallas interpreter; slow, for tests and rehearsals) or ``"sdpa"``.

Layout convention (paddle flash_attention): [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import jax

from paddle_tpu.ops.pallas import flash_attention as _fa
from paddle_tpu.ops.registry import API as _API


def flash_attention(query, key, value, causal=False, dropout=0.0,
                    training=True, impl=None):
    if impl is None:
        impl = ("pallas" if (jax.default_backend() == "tpu"
                             and dropout == 0.0
                             and _fa.tileable(query.shape[1])
                             and _fa.tileable(key.shape[1]))
                else "sdpa")
    if impl == "sdpa":
        return _API["scaled_dot_product_attention"](
            query, key, value, is_causal=causal, dropout_p=dropout,
            training=training)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown flash attention impl: {impl!r}")
    if dropout != 0.0:
        raise ValueError("the Pallas flash kernel has no dropout; "
                         "use impl='sdpa'")
    return _fa.flash_attention_op(query, key, value, causal=causal,
                                  interpret=(impl == "interpret"))
