"""Selective scan (Mamba-1) and its causal depthwise convolution over a
ragged-packed token stream, with per-sequence state carried in slots.

The serving step packs the new tokens of S sequence slots into one (T,)
stream (``cu_seqlens`` delimits the rows, ``context_lens`` is each row's
length AFTER the step: the contract of ``ops/pallas/
ragged_paged_attention.py``). A recurrent layer keeps, per sequence, what
a cache of keys and values cannot hold: the scan's state ``h`` and the
last ``taps - 1`` inputs of the convolution. Both live in **state slots**:
arrays ``(slots, ...)`` indexed by ``state_slots[i]`` for row i. A row
whose step starts at position 0 (``context_lens[i] == cu[i+1] - cu[i]``:
a new or re-prefilled request) starts from zero state whatever its slot
holds; any other row loads its slot at its first token. Every live row
stores its state after its last token. The last slot is a scratch slot:
padding rows are sent there.

``h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) (x) B_t``;
``y_t = h_t C_t`` (the skip ``D * x_t`` is the caller's). The state is
kept ``(N, E)``, state dim on sublanes and channels on lanes, in float32.

``impl``: ``None`` picks ``"pallas"`` on a TPU backend and ``"xla"``
elsewhere. ``"pallas"`` is the kernel of ``ops/pallas/selective_scan.py``
(``ragged_selective_scan`` in a compiled step's text): a row's state
stays in VMEM across its tokens, a decode row costs one micro-step and
two state copies. ``"interpret"`` runs that kernel through the Pallas
interpreter (slow, tests only). ``"xla"`` is the CPU's route and the
oracle the kernel is tested against: it walks the live rows and, per row,
its tokens in blocks of ``unroll`` (its own argument, no other route
reads it), both with dynamic trip counts. Anything else raises
``ValueError`` by name.

``mamba_mixer`` is the Mamba-1 mixer itself, from a layer's normed input
to its output projection, over that stream and those slots. This file
owns it: ``models/phi4flash.py`` (plain Mamba-1, which also reads the
scan's output before the gate) and ``models/jamba.py`` (RMSNorms on the
time-step input, B and C: ``inner_norm_eps``) both call it, so the two
models' state-space layers cannot drift apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.selective_scan import selective_scan_pallas

__all__ = ["selective_scan_step", "ragged_selective_scan",
           "ragged_causal_conv", "mamba_mixer"]


def selective_scan_step(h, x_t, dt_t, a_t, b_t, c_t):
    """The one-token (decode) form. ``h`` (..., N, E) float32; ``x_t``,
    ``dt_t`` (..., E); ``a_t`` (N, E) = A transposed; ``b_t``, ``c_t``
    (..., N). Returns (h', y_t (..., E))."""
    h = (jnp.exp(dt_t[..., None, :] * a_t) * h
         + (dt_t * x_t)[..., None, :] * b_t[..., :, None])
    return h, jnp.sum(h * c_t[..., :, None], axis=-2)


def _scan_xla(x, dt, a_t, b, c, state, slots, cu, ctx, num_seqs, unroll):
    t_total, e = x.shape
    n = a_t.shape[0]
    f32 = jnp.float32

    def pad(v):
        return jnp.pad(v.astype(f32), ((0, unroll), (0, 0)))

    xp, dtp, bp, cp = pad(x), pad(dt), pad(b), pad(c)
    scratch = state.shape[0] - 1

    def row(s, carry):
        y, st = carry
        lo = cu[s]
        nq = cu[s + 1] - lo
        slot = jnp.where(nq > 0, slots[s], scratch)
        h0 = jnp.where(ctx[s] - nq > 0, st[slot], 0.0)

        def block(i, inner):
            h, y = inner
            t0 = lo + i * unroll
            xb = jax.lax.dynamic_slice(xp, (t0, 0), (unroll, e))
            dtb = jax.lax.dynamic_slice(dtp, (t0, 0), (unroll, e))
            bb = jax.lax.dynamic_slice(bp, (t0, 0), (unroll, n))
            cb = jax.lax.dynamic_slice(cp, (t0, 0), (unroll, n))
            ys = []
            for k in range(unroll):
                h_new, y_k = selective_scan_step(h, xb[k], dtb[k], a_t,
                                                 bb[k], cb[k])
                # past the row's end the state stands still; what is
                # written there the next row overwrites
                h = jnp.where(i * unroll + k < nq, h_new, h)
                ys.append(y_k)
            return h, jax.lax.dynamic_update_slice(y, jnp.stack(ys),
                                                   (t0, 0))

        h, y = jax.lax.fori_loop(0, (nq + unroll - 1) // unroll, block,
                                 (h0, y))
        return y, st.at[slot].set(h)

    y, state = jax.lax.fori_loop(
        0, num_seqs, row,
        (jnp.zeros((t_total + unroll, e), f32), state))
    return y[:t_total], state


def ragged_selective_scan(x, dt, a, b, c, state, state_slots, cu_seqlens,
                          context_lens, num_seqs, *, impl=None, unroll=8):
    """``x``, ``dt`` (T, E); ``a`` (E, N) (negative); ``b``, ``c`` (T, N);
    ``state`` (slots, N, E) float32; ``state_slots`` (S,) int32. Returns
    (y (T, E) float32, state'). Rows past ``cu_seqlens[num_seqs]`` are
    padding: their y is zero or finite and no slot but the scratch one is
    touched for them."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown selective scan impl: {impl!r}")
    with jax.named_scope("ssm_scan"):
        args = (x, dt, jnp.transpose(a).astype(jnp.float32), b, c, state,
                state_slots.astype(jnp.int32), cu_seqlens.astype(jnp.int32),
                context_lens.astype(jnp.int32),
                jnp.asarray(num_seqs, jnp.int32))
        if impl == "xla":
            return _scan_xla(*args, unroll)
        return selective_scan_pallas(*args,
                                     interpret=(impl == "interpret"))


def ragged_causal_conv(x, w, bias, conv_state, state_slots, cu_seqlens,
                       context_lens, num_seqs):
    """Causal depthwise convolution over the ragged stream. ``x`` (T, E);
    ``w`` (taps, E), tap ``taps - 1`` on the current token; ``bias``
    (E,); ``conv_state`` (slots, taps - 1, E): a sequence's last
    ``taps - 1`` inputs, newest last. Returns (x' (T, E) before the
    activation, conv_state')."""
    with jax.named_scope("ssm_conv"):
        t_total, e = x.shape
        taps = w.shape[0]
        keep = taps - 1
        s_slots = state_slots.shape[0]
        cu = cu_seqlens.astype(jnp.int32)
        ctx = context_lens.astype(jnp.int32)
        ns = jnp.asarray(num_seqs, jnp.int32)
        t = jnp.arange(t_total, dtype=jnp.int32)
        seg = jnp.clip(jnp.searchsorted(cu, t, side="right") - 1, 0,
                       s_slots - 1).astype(jnp.int32)
        nq = cu[1:] - cu[:-1]                                   # (S,)
        live = (jnp.arange(s_slots) < ns) & (nq > 0)
        scratch = conv_state.shape[0] - 1
        slot = jnp.where(live, state_slots.astype(jnp.int32), scratch)
        old = jnp.where((ctx - nq > 0)[:, None, None], conv_state[slot],
                        jnp.zeros((), conv_state.dtype))   # (S, keep, E)
        local = t - cu[seg]
        out = x * w[keep] + bias
        for back in range(1, taps):
            # the input ``back`` tokens ago: in this step's stream, or in
            # the row's carried state when the row started later than that
            own = jnp.roll(x, back, axis=0)
            carried = old[seg, jnp.clip(keep + local - back, 0, keep - 1)]
            prev = jnp.where((local >= back)[:, None], own,
                             carried.astype(x.dtype))
            out = out + prev * w[keep - back]
        # new state: the last ``keep`` entries of [old state, row inputs]
        idx = nq[:, None] + jnp.arange(keep)[None, :]           # (S, keep)
        from_old = old[jnp.arange(s_slots)[:, None],
                       jnp.clip(idx, 0, keep - 1)]
        from_new = x[jnp.clip(cu[:-1, None] + idx - keep, 0, t_total - 1)]
        new = jnp.where((idx < keep)[:, :, None], from_old,
                        from_new.astype(conv_state.dtype))
        return out, conv_state.at[slot].set(new)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def mamba_mixer(p, u, state, state_slots, cu_seqlens, context_lens,
                num_seqs, *, scan_impl=None, inner_norm_eps=None):
    """The Mamba-1 mixer over the ragged stream. ``u`` (T, d) is the
    layer's normed input; ``p`` its weights as ``[in, out]`` matrices
    (``in_proj`` (d, 2E), ``conv_w`` (taps, E), ``conv_b``, ``x_proj``
    (E, R + 2N), ``dt_w`` (R, E), ``dt_b``, ``A_log`` (E, N), ``D``,
    ``out_proj`` (E, d)); ``state`` ``{"ssm": (slots, N, E) float32,
    "conv": (slots, taps - 1, E)}``. ``inner_norm_eps``: Jamba's RMSNorms
    on the time-step input, B and C (weights ``dt_norm``, ``b_norm``,
    ``c_norm``); None is plain Mamba-1. Returns (mixer output (T, d), the
    scan's output with the skip, before the gate (T, E) float32,
    state'). The products and gates are ``ssm_proj``; the convolution and
    the scan name themselves."""
    f32 = jnp.float32
    with jax.named_scope("ssm_proj"):
        xi, z = jnp.split(u @ p["in_proj"], 2, axis=-1)
        conv_in = (xi.astype(f32), p["conv_w"].astype(f32),
                   p["conv_b"].astype(f32))
    conv, conv_state = ragged_causal_conv(
        *conv_in, state["conv"], state_slots, cu_seqlens, context_lens,
        num_seqs)
    with jax.named_scope("ssm_proj"):
        xc = _silu(conv).astype(u.dtype)
        n, rank = p["A_log"].shape[1], p["dt_w"].shape[0]
        rbc = xc @ p["x_proj"]
        r, b, c = rbc[:, :rank], rbc[:, rank:rank + n], rbc[:, rank + n:]
        if inner_norm_eps is not None:
            r = _rms(r, p["dt_norm"], inner_norm_eps)
            b = _rms(b, p["b_norm"], inner_norm_eps)
            c = _rms(c, p["c_norm"], inner_norm_eps)
        dt = jax.nn.softplus(
            jnp.dot(r, p["dt_w"], preferred_element_type=f32)
            + p["dt_b"].astype(f32))
        a = -jnp.exp(p["A_log"].astype(f32))
    y, ssm_state = ragged_selective_scan(
        xc, dt, a, b, c, state["ssm"], state_slots, cu_seqlens,
        context_lens, num_seqs, impl=scan_impl)
    with jax.named_scope("ssm_proj"):
        y = y + p["D"].astype(f32) * xc.astype(f32)
        mix = (y * _silu(z.astype(f32))).astype(u.dtype) @ p["out_proj"]
    return mix, y, {"ssm": ssm_state, "conv": conv_state}
