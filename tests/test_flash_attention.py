"""Pallas flash attention vs XLA SDPA (the kernel asked for in interpret
mode by name on the CPU mesh — VERDICT.md round-1 item 2:
numerics-verify pallas vs SDPA)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.registry import API


def _sdpa_ref(q, k, v, causal):
    # plain [B,S,H,D] attention in f32
    qt = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)
    kt = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32)
    vt = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32)
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2:]
        # bottom-right aligned (reference FA2 semantics for sq != sk)
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.transpose(o, (0, 2, 1, 3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 128, 2, 64), (1, 256, 4, 32)])
def test_flash_forward_matches_reference(causal, shape):
    b, s, h, d = shape
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)
    out = fa.flash_attention_data(q, k, v, causal=causal, block_q=64,
                                  block_k=64, interpret=True)
    ref = _sdpa_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("sq,sk", [(64, 128), (128, 256), (64, 256)])
def test_flash_causal_cross_length_bottom_right(sq, sk):
    """ADVICE r2 (high): causal mask must be bottom-right aligned when
    q_seq != k_seq, matching the SDPA fallback and FA2 semantics."""
    b, h, d = 1, 2, 32
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, sq, h, d), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(b, sk, h, d), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(b, sk, h, d), dtype=jnp.float32)
    out = fa.flash_attention_data(q, k, v, causal=True, block_q=64,
                                  block_k=64, interpret=True)
    ref = _sdpa_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    def f_flash(q, k, v):
        return jnp.sum(fa.flash_attention_data(
            q, k, v, causal=True, block_q=64, block_k=64,
            interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, True) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    b, s, h, d = 1, 128, 2, 32
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)

    def f_flash(q, k, v):
        return jnp.sum(fa.flash_attention_data(
            q, k, v, causal=causal, block_q=64, block_k=64,
            interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-4)


def test_flash_attention_op_on_tape():
    """Tensor-level op participates in eager autograd."""
    paddle.seed(0)
    q = paddle.randn([1, 128, 2, 32])
    k = paddle.randn([1, 128, 2, 32])
    v = paddle.randn([1, 128, 2, 32])
    q.stop_gradient = False
    out = API["flash_attention"](q, k, v, causal=True, interpret=True)
    out.sum().backward()
    assert q.grad is not None
    assert q.grad.shape == [1, 128, 2, 32]


@pytest.mark.parametrize("impl", [None, "interpret", "sdpa"])
def test_entrypoint_runs_the_named_impl(impl):
    """The entry point's choice is a stated rule: off a TPU ``impl=None``
    is XLA SDPA (no pallas_call in the program), and the kernel runs in
    interpret mode only when asked for by name."""
    from paddle_tpu.ops import pallas_attention

    paddle.seed(0)
    q = paddle.randn([1, 256, 2, 32])
    k = paddle.randn([1, 256, 2, 32])
    v = paddle.randn([1, 256, 2, 32])
    out = pallas_attention.flash_attention(q, k, v, causal=True, impl=impl)
    ref = _sdpa_ref(q._data, k._data, v._data, True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)
    jaxpr = str(jax.make_jaxpr(
        lambda a, b, c: pallas_attention.flash_attention(
            a, b, c, causal=True, impl=impl)._data)(
                q._data, k._data, v._data))
    assert ("pallas_call" in jaxpr) == (impl == "interpret")


def test_entrypoint_never_interprets_unasked():
    """``impl="pallas"`` means the compiled kernel: off a TPU it raises,
    it does not quietly interpret or hand the call to SDPA; an unknown
    name and dropout into the kernel are errors too."""
    from paddle_tpu.ops import pallas_attention

    q = paddle.randn([1, 256, 2, 32])
    with pytest.raises(Exception, match="(?i)interpret"):
        pallas_attention.flash_attention(q, q, q, causal=True,
                                         impl="pallas").numpy()
    with pytest.raises(ValueError, match="unknown flash attention impl"):
        pallas_attention.flash_attention(q, q, q, impl="auto")
    with pytest.raises(ValueError, match="no dropout"):
        pallas_attention.flash_attention(q, q, q, dropout=0.1,
                                         impl="interpret")
