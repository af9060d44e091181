"""Flash attention as Pallas TPU kernels (forward + backward).

Reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu (+ SPMD
rule paddle/phi/infermeta/spmd_rules/flash_attention.cc). TPU-native
design: blockwise online-softmax over (q_block, k_block) grid tiles sized
for the MXU (128x128), accumulators in VMEM scratch, causal blocks skipped
entirely; backward recomputes P from saved logsumexp (no S materialized),
with separate dq and dk/dv kernels so each accumulates over its natural
grid order.

Public layout convention matches paddle flash_attention: [B, S, H, D].
Kernels operate on [B*H, S, D].

``interpret`` is never inferred: the kernels compile for the TPU unless a
caller passes ``interpret=True`` by name, which is how
tests/test_flash_attention.py verifies numerics against XLA SDPA on the
CPU mesh. Lowering the compiled kernel for a CPU raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from paddle_tpu.ops.pallas.common import declared, mxu_dot

_VMEM = pltpu.VMEM

# Large blocks amortize Mosaic per-tile overhead: measured on v5e at
# [4,2048,16,128] bf16 causal, 512x1024 runs ~2x faster than 128x128.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _pick_block(seq_len, preferred):
    """Largest block <= preferred that divides seq_len, stepping down
    through MXU-friendly sizes; sequences shorter than 128 (or with no
    dividing candidate) become a single whole-sequence block, which
    tileable() then gates on 8-alignment."""
    for b in (preferred, 512, 256, 128):
        if b <= preferred and b <= seq_len and seq_len % b == 0:
            return b
    return min(preferred, seq_len)
_NEG_INF = -1e30


def _vmem_spec(shape=None, index_map=None):
    if shape is None:
        return pl.BlockSpec(memory_space=_VMEM)
    return pl.BlockSpec(shape, index_map, memory_space=_VMEM)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                n_k, mask_off):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal (bottom-right aligned for sq != sk): skip blocks strictly
    # above the shifted diagonal row + mask_off >= col
    run = ((qi * block_q + block_q - 1 + mask_off >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            row = qi * block_q + mask_off + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse is [BH, 8, S] (8 sublanes to satisfy TPU tiling; row 0 real)
        row = (m_scr[:, :1] + jnp.log(l_safe))[:, 0]
        lse_ref[0] = jnp.broadcast_to(row[None, :], lse_ref.shape[1:])


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    """q,k,v: [BH, S, D] -> (o [BH, S, D], lse [BH, S])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    n_q = sq // block_q
    n_k = sk // block_k
    grid = (bh, n_q, n_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, mask_off=sk - sq)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            _vmem_spec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((block_q, 128), jnp.float32),
            _VMEM((block_q, 128), jnp.float32),
            _VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k, n_k,
                   mask_off):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = ((qi * block_q + block_q - 1 + mask_off >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            row = qi * block_q + mask_off + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = mxu_dot(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] = dq_scr[:] + mxu_dot(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k, n_q, mask_off):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = ((qi * block_q + block_q - 1 + mask_off >= ki * block_k)
           if causal else True)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            row = qi * block_q + mask_off + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, _NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv_scr[:] = dv_scr[:] + mxu_dot(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = mxu_dot(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] = dk_scr[:] + mxu_dot(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
               interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    n_q = sq // block_q
    n_k = sk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # [BH, S]
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, sq))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          mask_off=sk - sq),
        grid=(bh, n_q, n_k),
        in_specs=[
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            _vmem_spec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            _vmem_spec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=_vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[_VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          mask_off=sk - sq),
        grid=(bh, n_k, n_q),
        in_specs=[
            _vmem_spec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            _vmem_spec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
            _vmem_spec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            _VMEM((block_k, d), jnp.float32),
            _VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (on [BH, S, D])
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_flash(scale, causal, block_q, block_k, interpret):
    @jax.custom_vjp
    def fa(q, k, v):
        o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret)
        return o

    def fwd(q, k, v):
        o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                            interpret)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        return _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q,
                          block_k, interpret)

    fa.defvjp(fwd, bwd)
    return fa


def tileable(seq_len, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Whether the kernel tiles this sequence length: it must divide
    evenly into blocks that satisfy TPU sublane tiling (a multiple of
    8). The entry point sends every other length to XLA SDPA."""
    bq = _pick_block(seq_len, block_q)
    bk = _pick_block(seq_len, block_k)
    return not (seq_len % bq or seq_len % bk or bq % 8 or bk % 8)


def flash_attention_data(q, k, v, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False):
    """Raw-jnp flash attention on [B, S, H, D] inputs (differentiable).
    Under a declared ``kernel_mesh`` it runs per (batch, head) shard
    inside ``jax.shard_map``: GSPMD refuses to partition the kernel."""
    s, d = q.shape[1], q.shape[3]
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(sk, block_k)
    if s % block_q or sk % block_k:
        raise ValueError(
            f"flash_attention requires seq lengths divisible by the block "
            f"sizes; got q_seq={s} (block_q={block_q}), k_seq={sk} "
            f"(block_k={block_k}). Use ops.scaled_dot_product_attention "
            f"for ragged shapes.")
    fa = _make_flash(float(scale), bool(causal), int(block_q), int(block_k),
                     bool(interpret))

    def to_bh(x):
        xs = x.shape
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            xs[0] * xs[2], xs[1], xs[3])

    def local(q, k, v):             # shapes are the shard's own
        with jax.named_scope("attention"):
            o = fa(to_bh(q), to_bh(k), to_bh(v))
            return jnp.transpose(
                o.reshape(q.shape[0], q.shape[2], s, d), (0, 2, 1, 3))

    decl = declared()
    if decl is not None and (decl[1] is not None or decl[2] is not None):
        mesh, heads, batch = decl
        spec = PartitionSpec(batch, None, heads, None)
        local = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                              out_specs=spec, check_vma=False)
    return local(q, k, v)


def flash_attention_op(query, key, value, causal=False, interpret=False):
    """Tensor-level entry used by ops/pallas_attention.py; registers on the
    autograd tape via the registry emitter below."""
    from paddle_tpu.ops.registry import API as _API

    return _API["flash_attention"](query, key, value, causal=causal,
                                   interpret=interpret)


# register as a first-class op so eager autograd + AMP treat it like any
# other emitter (the reference registers flash_attn in its op yaml)
from paddle_tpu.ops import registry as _registry  # noqa: E402
from paddle_tpu.ops.registry import register_emitter as _register  # noqa


@_register
def flash_attention(q, k, v, causal=False, interpret=False):
    return flash_attention_data(q, k, v, causal=causal,
                                interpret=interpret)


if "flash_attention" not in _registry.OPS:
    _registry.build_registry([
        {"op": "flash_attention", "tensor_args": ["q", "k", "v"],
         "methods": []}])
