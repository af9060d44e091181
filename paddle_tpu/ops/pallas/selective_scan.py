"""Ragged selective scan (Mamba-1) as one Pallas kernel: a row's state
stays in VMEM for as long as the row lasts (``ops/selective_scan.py`` has
the contract and the mathematics; ``_scan_xla`` there is the oracle).

The grid is the token tiles of the stream (``block_t`` rows), walked in
order: ``x``, ``dt``, ``B``, ``C`` come in and ``y`` goes out as dense
``(block_t, E)`` / ``(block_t, N)`` tiles through Pallas's own pipeline,
``A`` (N, E) is fetched once, and the state array stays in HBM, aliased
onto the output; a tile past the last live token is not fetched, and its
``y`` is zeros. Inside a tile the kernel walks the rows that have tokens
in it (the row table ``cu_seqlens`` / ``context_lens`` / ``state_slots`` /
``num_seqs`` is scalar-prefetched). A row's ``(N, E)`` float32 state is
copied HBM -> VMEM once, at its first token (or zeroed there, for a row
that starts at position 0), updated in place one token at a time, and
copied VMEM -> HBM once, after its last token: a decode row costs one
micro-step and two copies, a chunk row ``nq`` micro-steps, and a row that
crosses a tile boundary keeps its buffer across the grid steps. Three
state buffers rotate, so while row r computes, the load of row r + 1 and
the store of row r - 1 are in flight; a buffer is refilled only after the
store that last left it was waited for, and the last grid step waits for
the stores still out. Live rows must hold distinct slots (the engine's
rule): row r + 1 is loaded before row r is stored.

A micro-step is ``selective_scan_step`` in float32, 128 lanes at a time:
``h = exp(dt * A) * h + (dt * x) * B``, ``y = sum_n h * C``. ``dt`` and
``dt * x`` are rows broadcast along sublanes; ``B`` and ``C`` are per-token
``(N,)`` vectors that multiply along SUBLANES, so once a tile they are
spread into ``(N, 128)`` images, lane-replicated (a masked lane reduce
and a lane broadcast a token), and a micro-step loads two of them for all
of E. ``dt * x`` is formed once a tile, densely, from the activations'
dtype. Rows of ``y`` that belong to no live row are zero. Rows with no
token and rows past ``num_seqs`` touch nothing, the scratch slot included.

Compiled it needs ``E % 128 == 0`` and ``N % 8 == 0``; the interpreter
takes any width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan_pallas"]

_VMEM = pltpu.VMEM
_BLOCK_T = 128
_BUFFERS = 3
# at (128, 5120): x, dt, y tiles twice over 13 MB, dt * x 2.6 MB, the B
# and C images 2 MB, A and three states 1.6 MB
_VMEM_LIMIT = 48 * 1024 * 1024


def _scan_kernel(cu_ref, ctx_ref, slot_ref, ns_ref,        # scalar prefetch
                 x_ref, dt_ref, b_ref, c_ref, a_ref, st_hbm,
                 y_ref, st_out, hbuf, dtx, bc32, bimg, cimg, ctl,
                 sem_ld, sem_st, *, lanes):
    block_t, e = dt_ref.shape
    n = a_ref.shape[0]
    last = ctx_ref.shape[0] - 1
    f32 = jnp.float32
    g = pl.program_id(0)
    t_lo = g * block_t
    t_hi = t_lo + block_t
    ns = ns_ref[0]
    n_live = jnp.clip(cu_ref[ns] - t_lo, 0, block_t)   # live tokens here

    def load(s, buf):
        return pltpu.make_async_copy(st_hbm.at[slot_ref[s]], hbuf.at[buf],
                                     sem_ld.at[buf])

    def store(s, buf):
        return pltpu.make_async_copy(hbuf.at[buf], st_out.at[slot_ref[s]],
                                     sem_st.at[buf])

    def carried(s):
        return ctx_ref[s] - (cu_ref[s + 1] - cu_ref[s]) > 0

    def fill(s, order):
        """Row ``s``, the ``order``-th live row of the call, gets buffer
        ``order % 3``: its slot's state on the way, or zeros."""
        buf = order % _BUFFERS

        @pl.when(order >= _BUFFERS)
        def _():
            store(s, buf).wait()        # the store that last left it

        @pl.when(carried(s))
        def _():
            load(s, buf).start()

        @pl.when(jnp.logical_not(carried(s)))
        def _():
            hbuf[buf] = jnp.zeros((n, e), f32)

    @pl.when(g == 0)
    def _():
        ctl[0] = 0          # first row not finished
        ctl[1] = 0          # live rows finished = the open row's order
        ctl[2] = 0          # the open row: 0 no buffer, 1 on the way, 2 in

    @pl.when(n_live > 0)
    def _():
        # dt * x for the tile, and B, C as lane-replicated (N, lanes)
        # images a token
        def block(i, _):
            rows = pl.ds(pl.multiple_of(i * 16, 16), 16)
            dtx[rows, :] = dt_ref[rows, :] * x_ref[rows, :].astype(f32)
            return 0

        jax.lax.fori_loop(0, (n_live + 15) // 16, block, 0)
        bc32[0] = b_ref[...].astype(f32)
        bc32[1] = c_ref[...].astype(f32)
        diag = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

        def image(i, _):
            base = pl.multiple_of(i * 8, 8)
            for k, img in ((0, bimg), (1, cimg)):
                tile = bc32[k, pl.ds(base, 8), :]                 # (8, N)
                for r in range(8):
                    col = jnp.sum(jnp.where(diag, tile[r:r + 1, :], 0.0),
                                  axis=1, keepdims=True)          # (N, 1)
                    at = pl.multiple_of((base + r) * n, n)
                    img[pl.ds(at, n), :] = jnp.broadcast_to(col,
                                                            (n, lanes))
            return 0

        jax.lax.fori_loop(0, (n_live + 7) // 8, image, 0)

    @pl.when(n_live < block_t)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def micro_step(j, buf):
        # Mosaic loads no single row at a dynamic index: a token's row
        # comes out of its aligned 8-row group by a sublane rotate, and
        # goes back into y's group under a sublane mask
        r = j % 8
        rows = pl.ds(pl.multiple_of(j - r, 8), 8)
        mine = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0) == r
        at = pl.ds(pl.multiple_of(j * n, n), n)
        bt, ct = bimg[at, :], cimg[at, :]
        for c0 in range(0, e, lanes):
            cols = pl.ds(c0, lanes)
            dt_t = pltpu.roll(dt_ref[rows, cols], (8 - r) % 8, 0)[0:1]
            dtx_t = pltpu.roll(dtx[rows, cols], (8 - r) % 8, 0)[0:1]
            h = (jnp.exp(dt_t * a_ref[:, cols]) * hbuf[buf, :, cols]
                 + dtx_t * bt)
            hbuf[buf, :, cols] = h
            y_t = jnp.sum(h * ct, axis=0, keepdims=True)
            pltpu.store(y_ref.at[rows, cols],
                        jnp.broadcast_to(y_t, (8, lanes)), mask=mine)

    def row_body(carry):
        # (row, its order among the live rows, what it has of a buffer:
        # ``ctl``'s three; whether the walk goes on)
        s, order, have, _ = carry
        lo, hi = cu_ref[s], cu_ref[s + 1]
        buf = order % _BUFFERS

        def live_row():
            @pl.when(have == 0)
            def _():
                fill(s, order)

            nxt = jnp.minimum(s + 1, last)
            ahead = ((s + 1 < ns) & (cu_ref[nxt + 1] > cu_ref[nxt])
                     & (cu_ref[nxt] < t_hi))

            @pl.when(ahead)
            def _():
                fill(nxt, order + 1)

            @pl.when((have < 2) & carried(s))
            def _():
                load(s, buf).wait()

            jax.lax.fori_loop(
                jnp.maximum(lo, t_lo) - t_lo, jnp.minimum(hi, t_hi) - t_lo,
                lambda j, _: (micro_step(j, buf), 0)[1], 0)
            ends = hi <= t_hi

            @pl.when(ends)
            def _():
                store(s, buf).start()

            done = ends.astype(jnp.int32)
            return (s + done, order + done,
                    jnp.where(ends, ahead.astype(jnp.int32), 2), done)

        return jax.lax.cond(hi > lo, live_row,
                            lambda: (s + 1, order, have, jnp.int32(1)))

    s, order, have, _ = jax.lax.while_loop(
        lambda c: (c[3] > 0) & (c[0] < ns) & (cu_ref[c[0]] < t_hi),
        row_body, (ctl[0], ctl[1], ctl[2], jnp.int32(1)))
    ctl[0], ctl[1], ctl[2] = s, order, have

    @pl.when(g == pl.num_programs(0) - 1)
    def _():
        for back in range(_BUFFERS):
            @pl.when(order > back)
            def _():
                store(0, (order - 1 - back) % _BUFFERS).wait()


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def selective_scan_pallas(x, dt, a_t, b, c, state, slots, cu, ctx,
                          num_seqs, block_t=_BLOCK_T, interpret=False):
    """``x`` (T, E), ``dt`` (T, E) float32, ``a_t`` (N, E) float32 = A
    transposed, ``b``, ``c`` (T, N), ``state`` (slots, N, E) float32,
    the row table int32. Returns (y (T, E) float32, state')."""
    t_total, e = x.shape
    n = a_t.shape[0]
    lanes = 128 if e % 128 == 0 else e
    if not interpret and (e % 128 or n % 8):
        raise NotImplementedError(
            f"the compiled selective scan needs channels in 128s and a "
            f"state dim in 8s: {e} channels, state dim {n}")
    i32 = jnp.int32

    def tile(g, *_):
        return (g, 0)

    def live_tile(g, cu, ctx, slots, ns):
        # past the last live token nothing is read: no tile is fetched
        return (jnp.minimum(g, jnp.maximum(cu[ns[0]] - 1, 0) // block_t), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(pl.cdiv(t_total, block_t),),
        in_specs=[
            pl.BlockSpec((block_t, e), live_tile, memory_space=_VMEM),
            pl.BlockSpec((block_t, e), live_tile, memory_space=_VMEM),
            pl.BlockSpec((block_t, n), live_tile, memory_space=_VMEM),
            pl.BlockSpec((block_t, n), live_tile, memory_space=_VMEM),
            pl.BlockSpec((n, e), lambda g, *_: (0, 0), memory_space=_VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_t, e), tile, memory_space=_VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            _VMEM((_BUFFERS, n, e), jnp.float32),
            _VMEM((block_t, e), jnp.float32),
            _VMEM((2, block_t, n), jnp.float32),
            _VMEM((block_t * n, lanes), jnp.float32),
            _VMEM((block_t * n, lanes), jnp.float32),
            pltpu.SMEM((3,), i32),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
        ],
    )
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t_total, e), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arguments: state is the 10th
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged_selective_scan",
    )(cu.astype(i32), ctx.astype(i32), slots.astype(i32),
      jnp.reshape(num_seqs.astype(i32), (1,)), x, dt, b, c, a_t, state)
    return y, state
