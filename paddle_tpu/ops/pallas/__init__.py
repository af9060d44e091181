"""Pallas TPU kernels — the hot-path custom kernels the reference ships as
fused CUDA (paddle/phi/kernels/gpu/flash_attn_kernel.cu, fusion/).

Kernels compile for the TPU. Interpret mode (numerics verifiable without
hardware) happens only when a caller asks for it by name; nothing infers
it from the backend.
"""
from paddle_tpu.ops.pallas import flash_attention  # noqa: F401
from paddle_tpu.ops.pallas import ragged_paged_attention  # noqa: F401
