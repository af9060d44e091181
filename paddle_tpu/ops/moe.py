"""Serving's expert layer: a router that picks ``top_k`` of E experts a
row, and a DROPLESS expert FFN over the ragged ``(T,)`` token stream.

No capacity and no dropped token: the live rows' ``T x top_k``
assignments are sorted by expert, each expert's rows then lie together,
and two grouped matrix products (gate/up, down) run over the E groups of
uneven size; the rows go back to their tokens weighted. Rows that are
padding of the stream (``live`` false) are routed nowhere and counted
nowhere. (``incubate/moe/moe_layer.py`` is the training-side GShard layer
with capacity drops; it is not on the serving path.)

The grouped product is ``ops/pallas/grouped_matmul.py``: the repo's own
Pallas kernel on a TPU, ``jax.lax.ragged_dot`` (plain XLA) elsewhere;
``impl=`` is the only selector, as the attention op has one.

A layer that holds a SHARE of the model's experts (``first_expert`` and
the matrices of the ``held`` experts from there; the others live on the
chips that share the layer with this one) is routed over all of them:
scores, bias, top-k and the normalising sum run over the router's whole
width, and only the assignments to held experts are sorted, multiplied
and summed back. What an absent expert would add is left out, and nothing
stands in for it or for the exchange that would carry it;
``rows_per_expert`` is ``(held,)``. A layer that holds every expert
(``first_expert`` 0, as many matrices as the router has columns) is the
program it was before the share existed.

Routers: ``route_sigmoid_topk`` (DeepSeek-V3's ``noaux_tc`` with one
group): scores ``s = sigmoid(W_g u)`` in float32; SELECTION by
``s + bias``; WEIGHTS from the scores without the bias, normalised over
the chosen set and scaled. ``route_softmax_topk`` (LongCat-Flash): ``p =
softmax(W_g u)`` in float32 over the router's whole width, selection by
``p + bias``, weights ``scale * p`` of the chosen, NOT normalised.

IDENTITY ("zero-compute") experts: a router may be wider than the routed
experts, its columns from ``zero_experts`` on naming experts whose output
is their input. ``dropless_expert_ffn(zero_experts=)`` keeps assignments
to them out of the sort (as those to an absent expert) and adds ``w * u``
for each; ``zero_expert_counts`` counts them for the step's counters.
Without ``zero_experts`` the layer is the program it was before they
existed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

__all__ = ["route_sigmoid_topk", "route_softmax_topk", "grouped_matmul",
           "dropless_expert_ffn", "zero_expert_counts"]


def route_sigmoid_topk(u, w_router, select_bias, *, top_k, scale,
                       normalize=True):
    """``u`` (T, d), ``w_router`` (d, E) and ``select_bias`` (E,) in
    float32. Returns (chosen experts (T, top_k) int32, weights (T, top_k)
    float32, scores + bias (T, E) float32)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(u.astype(f32), w_router.astype(f32),
                               precision=jax.lax.Precision.HIGHEST))
    biased = s + select_bias.astype(f32)
    _, chosen = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * scale, biased


def route_softmax_topk(u, w_router, select_bias, *, top_k, scale):
    """``u`` (T, d), ``w_router`` (d, E) and ``select_bias`` (E,) in
    float32: ``p = softmax(u W)`` over all E columns, the ``top_k``
    largest ``p + bias`` chosen, weights ``scale * p`` of the chosen
    (unnormalised). Returns (chosen (T, top_k) int32, weights (T, top_k)
    float32, p + bias (T, E) float32)."""
    f32 = jnp.float32
    p = jax.nn.softmax(jnp.dot(u.astype(f32), w_router.astype(f32),
                               precision=jax.lax.Precision.HIGHEST),
                       axis=-1)
    biased = p + select_bias.astype(f32)
    _, chosen = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen.astype(jnp.int32), w * scale, biased


def zero_expert_counts(chosen, live, zero_experts):
    """(2,) int32: the live rows' assignments to identity experts (ids
    from ``zero_experts`` on), and all the live rows' assignments."""
    i32 = jnp.int32
    zero = jnp.sum(live[:, None] & (chosen >= zero_experts), dtype=i32)
    return jnp.stack([zero, jnp.sum(live, dtype=i32) * chosen.shape[1]])


def dropless_expert_ffn(u, chosen, weights, gate_up, down, live, *,
                        impl=None, first_expert=None, zero_experts=None):
    """``sum_k weights[t, k] * E_{chosen[t, k]}(u[t])`` over the held
    experts, with ``E_e(u) = down[e](SiLU(g) * v)``, ``[g | v] =
    gate_up[e] u``.

    ``u`` (T, d); ``chosen``/``weights`` (T, K); ``gate_up`` (E, d, 2f);
    ``down`` (E, f, d); ``live`` (T,) bool, false on the stream's padding
    rows. ``first_expert`` None: the E matrices are all the experts
    ``chosen`` names. An int: they are experts ``first_expert ..
    first_expert + E`` of a wider router, and an assignment to any other
    is dropped from the sort (it adds nothing). ``zero_experts`` None: no
    identity experts. An int: ids from there on are identity experts,
    kept out of the sort, each adding ``weights[t, k] * u[t]``. Returns
    (out (T, d) in ``u``'s dtype, rows_per_expert (E,) int32: the live
    rows' assignments by held expert). ``impl``: the grouped product's
    (None: Pallas on a TPU, XLA elsewhere)."""
    t, k = chosen.shape
    e = gate_up.shape[0]
    f32 = jnp.float32
    with jax.named_scope("moe_dispatch"):
        identity = held = None
        if zero_experts is not None:
            identity = live[:, None] & (chosen >= zero_experts)
        if first_expert is not None:
            chosen = chosen - first_expert
            held = (chosen >= 0) & (chosen < e)
        elif zero_experts is not None:
            held = chosen < e

        def counts(trailing=()):
            """Which assignments count: the live rows', and of a share
            those to held experts; (T, 1 or K) + ``trailing`` axes."""
            if held is None:
                return live[(slice(None), None) + trailing]
            return (live[:, None] & held)[(...,) + trailing]

        # a padding row's assignments (and those to experts not held)
        # sort behind every expert's
        flat = jnp.where(counts(), chosen, e).reshape(-1)
        order = jnp.argsort(flat, stable=True)
        rows_per_expert = jnp.zeros((e,), jnp.int32).at[flat].add(
            1, mode="drop")
        xs = u[order // k]                                    # (A, d)
    with jax.named_scope("moe_experts"):
        gv = grouped_matmul(xs, gate_up, rows_per_expert, impl=impl)
        g, v = jnp.split(gv, 2, axis=-1)
        act = (jax.nn.silu(g) * v).astype(u.dtype)
        y = grouped_matmul(act, down, rows_per_expert, impl=impl)  # f32
    with jax.named_scope("moe_dispatch"):
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        y = y[inverse].reshape(t, k, -1)
        w = jnp.where(counts(), weights.astype(f32), 0.0)
        # where, not multiply: a padding row's y was never computed
        y = jnp.where(counts((None,)), y, 0.0)
        out = jnp.einsum("tk,tkd->td", w, y)
        if identity is not None:
            # an identity expert's output is its input
            out = out + jnp.sum(jnp.where(identity, weights.astype(f32),
                                          0.0), axis=1)[:, None] * u
    return out.astype(u.dtype), rows_per_expert
