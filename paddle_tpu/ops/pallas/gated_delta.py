"""One step of the gated delta rule for every decode row, as one Pallas
kernel (``ops/gated_delta.py`` has the contract and the mathematics;
``_decode_rows_xla`` there is the oracle).

The grid is the rows of the step's table. Row r's state, the ``(dk, H *
dv)`` float32 matrix of its slot (``slot[r]``, scalar-prefetched; the
scratch slot for a row that is not a decode row), comes in and goes back
through Pallas's own pipeline, aliased in place: each decode row's state
is read once and written once, the next row's in flight while this one
computes. Consecutive rows on the scratch slot share one copy.

The row's own token comes in as ``q``, ``k`` (dk, H) (a head a lane
column), ``v`` (1, H * dv) and the decay and step size ``(2, H)``. The
step walks the heads in GROUPS whose lanes are a whole number of 128-lane
tiles (two heads of 192 lanes: 384): a group's key and query columns are
spread over their heads' lanes by a lane broadcast and a select, its
decay and step size likewise, and then it is elementwise work and two
sublane reductions on ``(dk, group lanes)``, all float32:

    S = a S;  kv = sum_k S * k;  S += k * (beta (v - kv));  o = sum_k S * q

A row whose slot is not carried (its step starts at position 0) starts
from zeros, selected, never multiplied (the scratch slot may hold
anything).

Compiled it needs ``dk % 8 == 0`` and the group's lanes in 128s; the
interpreter takes any width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_decode_pallas"]

_VMEM = pltpu.VMEM


def _decode_kernel(slot_ref, carried_ref,                 # scalar prefetch
                   q_ref, k_ref, v_ref, ab_ref, s_in, o_ref, s_out, *,
                   dv, group):
    dk, heads = k_ref.shape
    lanes = dv * group
    r = pl.program_id(0)
    carried = carried_ref[r] > 0
    # which head of the group each lane belongs to
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // dv

    def spread(ref, h0, rows):
        """Columns h0 .. h0 + group - 1 of ``ref`` over their heads'
        lanes: (rows, lanes)."""
        out = jnp.broadcast_to(ref[:, h0:h0 + 1], (rows, lanes))
        for i in range(1, group):
            out = jnp.where(head_of == i, jnp.broadcast_to(
                ref[:, h0 + i:h0 + i + 1], (rows, lanes)), out)
        return out

    for h0 in range(0, heads, group):
        cols = pl.ds(h0 * dv, lanes)
        kx, qx = spread(k_ref, h0, dk), spread(q_ref, h0, dk)
        ab = spread(ab_ref, h0, 2)
        s = jnp.where(carried, s_in[:, cols], 0.0) * ab[0:1]
        kv = jnp.sum(s * kx, axis=0, keepdims=True)
        s = s + kx * (ab[1:2] * (v_ref[:, cols] - kv))
        s_out[:, cols] = s
        o_ref[:, cols] = jnp.sum(s * qx, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_decode_pallas(q, k, v, ab, state, slot, carried,
                              interpret=False):
    """``q``, ``k`` (S, dk, H) float32, a row's token with heads on lanes;
    ``v`` (S, 1, H * dv); ``ab`` (S, 2, H): the decay exp(g) and beta;
    ``state`` (slots, dk, H * dv) float32; ``slot`` (S,) int32 the slot a
    row reads and writes; ``carried`` (S,) int32. Returns (o (S, 1, H *
    dv) float32, state')."""
    s_rows, dk, heads = k.shape
    width = v.shape[-1]
    dv = width // heads
    # heads a group: the fewest whose lanes fill whole 128-lane tiles,
    # if they divide the heads (the interpreter takes what divides)
    group = math.gcd(128 // math.gcd(dv, 128), heads)
    if not interpret and (dk % 8 or group * dv % 128):
        raise NotImplementedError(
            f"the compiled gated delta step needs a key width in 8s and "
            f"heads in groups of 128-lane tiles: dk {dk}, {heads} heads "
            f"of {dv}")

    def row(r, *_):
        return (r, 0, 0)

    def slot_of(r, slot, carried):
        return (slot[r], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_rows,),
        in_specs=[
            pl.BlockSpec((None, dk, heads), row, memory_space=_VMEM),
            pl.BlockSpec((None, dk, heads), row, memory_space=_VMEM),
            pl.BlockSpec((None, 1, width), row, memory_space=_VMEM),
            pl.BlockSpec((None, 2, heads), row, memory_space=_VMEM),
            pl.BlockSpec((None, dk, width), slot_of, memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, width), row, memory_space=_VMEM),
            pl.BlockSpec((None, dk, width), slot_of, memory_space=_VMEM),
        ],
    )
    # the state in and out, double-buffered, and a group's temporaries
    need = 4 * dk * width * 4 + 8 * dk * 128 * group * 4
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, dv=dv, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s_rows, 1, width), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arguments: state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 16 * 2 ** 20),
        interpret=interpret,
        name="ragged_gated_delta",
    )(slot.astype(jnp.int32), carried.astype(jnp.int32), q, k, v, ab,
      state)
    return o, state
