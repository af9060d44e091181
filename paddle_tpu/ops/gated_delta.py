"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464) over a
ragged-packed token stream, with per-sequence state carried in slots.

The stream, the row table (``cu_seqlens``, ``context_lens`` AFTER the
step, ``num_seqs``) and the state slots are ``ops/selective_scan.py``'s:
a row whose step starts at position 0 starts from zero state whatever its
slot holds, any other row loads its slot, every live row stores its state
after its last token, and the last slot is scratch.

Per head, with a key ``k_t`` and a query ``q_t`` of ``dk`` lanes and a
value ``v_t`` of ``dv``, a decay ``a_t = exp(g_t)`` (``g_t <= 0``) and a
step size ``beta_t`` (up to 2 where negative eigenvalues are allowed),
the state ``S`` (dk, dv) follows

    S_t = a_t S_{t-1} + k_t (beta_t (v_t - a_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

In a slot the state of all heads is ONE ``(dk, H * dv)`` float32 matrix,
head h on lanes ``[h * dv, (h + 1) * dv)``: at 96 x (30 x 192) it is 12
sublane tiles by 45 lane tiles with no padding, where ``(H, dk, dv)``
would pad every head's 192 lanes to 256.

Two forms, one result:

* a row of more than one token (a prompt chunk) takes the chunked form
  (``gated_delta_chunk``): ``chunk`` tokens at a time, the WY/UT
  transform of the paper, a unit lower-triangular solve and matrix
  products under ``highest`` precision, from the row's state in to its
  state out. It is XLA on every backend: a loop over the step's chunk
  rows only, and per row over its chunks, both with dynamic trip counts.
* a row of one token (a decode row) takes one step of the recurrence
  (``gated_delta_step``): with ``impl`` ``"pallas"`` the kernel of
  ``ops/pallas/gated_delta.py`` (``ragged_gated_delta`` in a compiled
  step's text), which reads and writes each decode row's state once;
  with ``"xla"`` the same step on all rows at once in ``jax.numpy``
  (the CPU's route and the kernel's oracle); ``"interpret"`` runs the
  kernel through the Pallas interpreter (tests only).

``impl`` None picks ``"pallas"`` on a TPU backend and ``"xla"``
elsewhere; anything else raises ``ValueError`` by name.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.gated_delta import gated_delta_decode_pallas

__all__ = ["gated_delta_step", "gated_delta_chunk", "ragged_gated_delta"]

HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(s, q, k, v, decay, beta):
    """One token in the slot layout: ``s`` (..., dk, H, dv) float32;
    ``q``, ``k`` (..., H, dk); ``v`` (..., H, dv); ``decay`` (= exp(g)),
    ``beta`` (..., H). Returns (s', o (..., H, dv))."""
    s = s * decay[..., None, :, None]
    kv = jnp.einsum("...khv,...hk->...hv", s, k, precision=HIGHEST)
    delta = beta[..., None] * (v - kv)
    s = s + jnp.swapaxes(k, -1, -2)[..., None] * delta[..., None, :, :]
    return s, jnp.einsum("...khv,...hk->...hv", s, q, precision=HIGHEST)


def gated_delta_chunk(s0, q, k, v, g, beta):
    """``C`` tokens of one row from the state ``s0`` (H, dk, dv): ``q``,
    ``k`` (C, H, dk), ``v`` (C, H, dv), ``g`` (log decay), ``beta`` (C,
    H), all float32. A token past the row's end has ``k``, ``g`` and
    ``beta`` zero: it changes nothing. Returns (s', o (C, H, dv)).

    With ``G`` the decays' cumulative sum in the chunk, the state after
    token t is ``e^{G_t} S_0 + sum_{i<=t} e^{G_t - G_i} k_i u_i^T``, where
    ``(I + diag(beta) A) u = diag(beta) (V - diag(e^G) K S_0)`` and
    ``A[t, i] = e^{G_t - G_i} k_t . k_i`` below the diagonal: one
    triangular solve gives ``U`` and ``W`` with ``u = U - W S_0``."""
    def ein(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    qh, kh, vh = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))   # (H, C, .)
    cg = jnp.cumsum(jnp.swapaxes(g, 0, 1), axis=-1)            # (H, C)
    b = jnp.swapaxes(beta, 0, 1)
    c = cg.shape[-1]
    i = jnp.arange(c)
    strict, causal = i[:, None] > i[None, :], i[:, None] >= i[None, :]
    diff = cg[:, :, None] - cg[:, None, :]
    # the decay between two tokens, exponentiated only where it is <= 0
    dec = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    lower = jnp.eye(c, dtype=jnp.float32) + jnp.where(
        strict, b[:, :, None] * dec * ein("htd,hid->hti", kh, kh), 0.0)
    eg = jnp.exp(cg)
    rhs = jnp.concatenate([b[..., None] * vh, (b * eg)[..., None] * kh],
                          axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    dv = vh.shape[-1]
    u = sol[..., :dv] - ein("htk,hkv->htv", sol[..., dv:], s0)
    o = (eg[..., None] * ein("htk,hkv->htv", qh, s0)
         + ein("hti,hiv->htv", dec * ein("htd,hid->hti", qh, kh), u))
    last = cg[:, -1:]
    s = (jnp.exp(last)[..., None] * s0
         + ein("hik,hiv->hkv", kh * jnp.exp(last - cg)[..., None], u))
    return s, jnp.swapaxes(o, 0, 1)


def _chunk_rows(q, k, v, g, beta, state, slots, cu, ctx, num_seqs, chunk):
    """Every live row of more than one token through the chunked form.
    Returns (o (T, H, dv), state'); o is zero at every other row."""
    t_total, heads, dk = k.shape
    dv = v.shape[-1]
    s_slots = slots.shape[0]
    nq = cu[1:] - cu[:-1]
    multi = (jnp.arange(s_slots) < num_seqs) & (nq > 1)
    rows = jnp.nonzero(multi, size=s_slots, fill_value=0)[0]
    f32 = jnp.float32

    def pad(a):
        return jnp.pad(a.astype(f32),
                       ((0, chunk),) + ((0, 0),) * (a.ndim - 1))

    qp, kp, vp, gp, bp = map(pad, (q, k, v, g, beta))

    def row(j, carry):
        out, st = carry
        s = rows[j]
        lo, n, slot = cu[s], nq[s], slots[s]
        # (dk, H * dv) -> (H, dk, dv) for this row's slot only
        mine = jnp.transpose(st[slot].reshape(dk, heads, dv), (1, 0, 2))
        h0 = jnp.where(ctx[s] - n > 0, mine, 0.0)

        def block(i, inner):
            h, out = inner
            t0 = lo + i * chunk
            live = (i * chunk + jnp.arange(chunk)) < n

            def take(a):
                return jax.lax.dynamic_slice_in_dim(a, t0, chunk)

            keep = live[:, None]
            h, o = gated_delta_chunk(
                h, take(qp), jnp.where(keep[..., None], take(kp), 0.0),
                take(vp), jnp.where(keep, take(gp), 0.0),
                jnp.where(keep, take(bp), 0.0))
            o = jnp.where(keep[..., None], o, take(out))
            return h, jax.lax.dynamic_update_slice_in_dim(out, o, t0, 0)

        h, out = jax.lax.fori_loop(0, (n + chunk - 1) // chunk, block,
                                   (h0, out))
        back = jnp.transpose(h, (1, 0, 2)).reshape(dk, heads * dv)
        return out, st.at[slot].set(back)

    out, state = jax.lax.fori_loop(
        0, jnp.sum(multi), row,
        (jnp.zeros((t_total + chunk, heads, dv), f32), state))
    return out[:t_total], state


def _decode_rows_xla(q, k, v, decay, beta, state, slot, carried):
    """One step for every row at once, in ``jax.numpy``: the rows' own
    tokens ``q``, ``k`` (S, H, dk), ``v`` (S, H, dv), ``decay``, ``beta``
    (S, H); ``slot`` (S,) the slot each row reads and writes. Returns
    (o (S, H, dv), state')."""
    s_rows, heads, dk = k.shape
    dv = v.shape[-1]
    h = state[slot].reshape(s_rows, dk, heads, dv)
    h = jnp.where(carried[:, None, None, None], h, 0.0)
    h, o = gated_delta_step(h, q, k, v, decay, beta)
    return o, state.at[slot].set(h.reshape(s_rows, dk, heads * dv))


def ragged_gated_delta(q, k, v, g, beta, state, state_slots, cu_seqlens,
                       context_lens, num_seqs, *, impl=None, chunk=64):
    """``q``, ``k`` (T, H, dk) (``q`` scaled, both L2-normalised by the
    caller); ``v`` (T, H, dv); ``g`` (T, H) the log decay (<= 0);
    ``beta`` (T, H); ``state`` (slots, dk, H * dv) float32;
    ``state_slots`` (S,) int32. Returns (o (T, H, dv) float32, state').
    Rows past ``cu_seqlens[num_seqs]`` are padding: their output is
    zero and no slot but the scratch one is touched for them."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown gated delta impl: {impl!r}")
    f32, i32 = jnp.float32, jnp.int32
    with jax.named_scope("gdn_delta"):
        t_total, heads, dk = k.shape
        dv = v.shape[-1]
        slots = state_slots.astype(i32)
        cu = cu_seqlens.astype(i32)
        ctx = context_lens.astype(i32)
        ns = jnp.asarray(num_seqs, i32)
        out, state = _chunk_rows(q, k, v, g, beta, state, slots, cu, ctx,
                                 ns, chunk)
        # the decode rows: one token each, at the row's first stream row;
        # every other row reads and writes the scratch slot
        nq = cu[1:] - cu[:-1]
        one = (jnp.arange(slots.shape[0]) < ns) & (nq == 1)
        at = jnp.clip(cu[:-1], 0, t_total - 1)
        slot = jnp.where(one, slots, state.shape[0] - 1)
        carried = ctx - nq > 0
        rq, rk, rv = q[at].astype(f32), k[at].astype(f32), v[at].astype(f32)
        decay, rb = jnp.exp(g[at].astype(f32)), beta[at].astype(f32)
        if impl == "xla":
            o, state = _decode_rows_xla(rq, rk, rv, decay, rb, state, slot,
                                        carried)
        else:
            o, state = gated_delta_decode_pallas(
                jnp.swapaxes(rq, 1, 2), jnp.swapaxes(rk, 1, 2),
                rv.reshape(-1, 1, heads * dv), jnp.stack([decay, rb], 1),
                state, slot, carried.astype(i32),
                interpret=(impl == "interpret"))
            o = o.reshape(-1, heads, dv)
        out = out.at[jnp.where(one, at, t_total)].set(o, mode="drop")
        return out, state
