"""Chip probe for the latent attention calls at their cells' shapes: the
kernel of ``ops/pallas/sparse_latent_attention.py`` alone, ``--layers``
calls a timed run (a step's worth), for several (product rows a tile,
tokens a page group):

* ``--call selected`` (default): a full layer of
  `dots3-note-prev-d5.longdoc-c16` (512 stream rows, 16 slots of 32,768
  tokens, 128 heads of 640 lanes, top-2,048) on mixed steps whose chunk
  sits at several depths of its prompt and on a decode-only step;
* ``--call plain``: a layer of `kimi-vl-a3b-d8.vqa-c32` (512 rows, 32
  slots of 8,192 tokens, 16 heads of 640 lanes, pool of 16,384 blocks):
  a 480-row chunk 2k deep alone, 30 decode rows at ~2,800 alone, both;
* ``--call window``: a sliding layer of the dots3 cell (64 heads of 1,152
  lanes under a 513-key window): a 497-row chunk beside 15 decode rows,
  and the decode rows alone.

    chiprun -- env PYTHONPATH=. python scripts/sparse_attention_probe.py

With ``PYTHONPATH`` at a checkout from before PR 38, ``plain`` and
``window`` time that checkout's body (the ragged kernel's latent mode,
the windowed call in head groups of 8) at its own tiling: the first
reading of PR 38. Needs a TPU (exits 2 without one: a CPU time is no
device time). Prints one JSON line a case: median, fastest and slowest of
``--reps`` timed runs in ms A CALL, each closed by ``block_until_ready``,
and the (row, key) pairs the rows see (and select).
"""
import argparse
import inspect
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BS = 16
SHAPES = {     # stream rows, slots, blocks a slot, pool, heads, lanes, value
    "selected": dict(t=512, s=16, mb=2048, nb=32768, heads=128, lanes=640,
                     v=512),
    "plain": dict(t=512, s=32, mb=512, nb=16384, heads=16, lanes=640, v=512),
    "window": dict(t=512, s=16, mb=2048, nb=1088, heads=64, lanes=1152,
                   v=1024),
}
TOP, WINDOW = 2048, 513


def timed(fn, *args, reps):
    ms = []
    for i in range(reps + 2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        if i >= 2:                               # compile, then one warm
            ms.append((time.perf_counter() - t0) * 1e3)
    ms.sort()
    return out, dict(ms_median=round(ms[len(ms) // 2], 3),
                     ms_min=round(ms[0], 3), ms_max=round(ms[-1], 3))


def stream(t, s, chunk, chunk_ctx, decode_ctx):
    """(cu, ctx, ns): a ``chunk``-row chunk that ends at ``chunk_ctx``
    (none if 0), then one decode row a context of ``decode_ctx``."""
    lens = ([chunk] if chunk else []) + [1] * len(decode_ctx)
    cu = np.concatenate([[0], np.cumsum(lens)])
    cu = np.concatenate([cu, np.full(s + 1 - len(cu), cu[-1])])
    ctx = np.zeros((s,), np.int32)
    ctx[:len(lens)] = ([chunk_ctx] if chunk else []) + list(decode_ctx)
    return cu.astype(np.int32), ctx, np.int32(len(lens))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--call", choices=sorted(SHAPES), default="selected")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 2
    from paddle_tpu.ops import sparse_index
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    z = SHAPES[a.call]
    t, s, mb, nb = z["t"], z["s"], z["mb"], z["nb"]
    rng = np.random.default_rng(a.seed)
    if a.call == "window":
        # a window pool: each slot's table names the few live blocks at
        # the END of its 2,048 entries' worth of context, the rest -1
        bt = np.full((s, mb), -1, np.int32)
    else:
        bt = rng.permutation(nb).astype(np.int32).reshape(s, mb)
    k1, k2, k3 = jax.random.split(jax.random.key(a.seed), 3)
    q = jax.random.normal(k1, (a.layers, t, z["heads"], z["lanes"]),
                          jnp.bfloat16)
    pool = jax.random.normal(k2, (nb, BS, z["lanes"]), jnp.bfloat16)
    # a checkout from before PR 38: the latent mode of the ragged kernel
    old_body = "window" not in inspect.signature(
        sla._attend_pallas.__wrapped__).parameters
    print(json.dumps(dict(device=jax.devices()[0].device_kind, call=a.call,
                          body="ragged kernel" if old_body else "latent")),
          flush=True)

    def case(name, rows, rows_product, group, **more):
        """One timed call: ``rows`` = (chunk rows, the context its last
        row ends at, the decode rows' contexts)."""
        sla._PRODUCT_ROWS, sla._GROUP_TOKENS = rows_product, group
        cu, ctx, ns = stream(t, s, *rows)
        table = bt
        if a.call == "window":
            table = bt.copy()
            for i in range(int(ns)):
                first = max(int(ctx[i]) - int(cu[i + 1] - cu[i])
                            - WINDOW + 1, 0) // BS
                n = -(-int(ctx[i]) // BS) - first
                table[i, first:first + n] = i * 68 + np.arange(n)
        cu, ctx, ns, table = (jnp.asarray(x) for x in (cu, ctx, ns, table))
        _, pos, _ = sla._token_layout(t, s, cu, ctx, ns)
        mask = None
        if a.call == "selected":
            at = jnp.arange(mb * BS, dtype=jnp.int32)[None, :]
            noise = jax.random.normal(k3, (t, mb * BS), jnp.float32)
            mask = jax.jit(lambda x: sparse_index.select_topk(x, TOP))(
                jnp.where(at <= pos[:, None], noise, -jnp.inf))
        if old_body and a.call != "selected":
            from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

            if more:
                more["head_block"] = 8

            def one(qi, pool, mask, *rest):
                return rpa._ragged_attend_pallas.__wrapped__(
                    qi, pool, None, *rest, 0.07, interpret=False,
                    v_lanes=z["v"], **more)
        else:
            def one(qi, pool, mask, *rest):
                return sla._attend_pallas.__wrapped__(
                    qi, pool, mask, *rest, scale=0.07, v_lanes=z["v"],
                    interpret=False, **more)

        # a jit of its own a case: the constants above are read at trace
        fn = jax.jit(lambda qs, *rest: jax.lax.map(
            lambda qi: one(qi, *rest), qs))
        said = dict(case=name, product_rows=rows_product,
                    group_tokens=group, **more)
        try:
            out, ms = timed(fn, q, pool, mask, table, cu, ctx, ns,
                            reps=a.reps)
        except Exception as e:          # a tiling the compiler refuses
            print(json.dumps(dict(said, refused=str(e)[-300:])), flush=True)
            return
        ms = {k: round(v / a.layers, 4) for k, v in ms.items()}
        live = np.asarray(pos)[np.asarray(pos) >= 0]
        seen = (np.minimum(live + 1, WINDOW) if more else live + 1).sum()
        print(json.dumps(dict(
            said, chunk_ctx=rows[1], **ms, pairs_seen=int(seen),
            **({} if mask is None else {"pairs_selected":
                                        int(jnp.sum(mask))}),
            finite=bool(jnp.isfinite(out.astype(jnp.float32)).all()))),
            flush=True)

    if a.call == "selected":
        decode = list(rng.integers(3000, 31000, 15))
        for rows_product, group in ((1024, 512), (2048, 512), (1024, 1024),
                                    (2048, 1024), (1024, 256)):
            case("mixed step", (497, 11000, decode), rows_product, group)
        for chunk_ctx in (1500, 4000, 7000, 20000, 30000):
            case("mixed step", (497, chunk_ctx, decode), 2048, 512)
        case("decode-only step", (0, 0, decode), 2048, 512)
        case("decode-only step", (0, 0, decode), 2048, 1024)
    elif a.call == "plain":
        decode = list(rng.integers(1000, 4600, 30))
        kinds = (("chunk alone", (480, 2528, [])),
                 ("decode rows alone", (0, 0, decode)),
                 ("mixed step", (480, 2528, decode)))
        for name, rows in kinds:
            case(name, rows, 2048, 512)
        for rows_product, group in ((1024, 512), (2048, 256), (2048, 1024),
                                    (1024, 1024), (1024, 256)):
            case("mixed step", kinds[2][1], rows_product, group)
        case("mixed step", (480, 6000, decode), 2048, 512)
    else:
        decode = list(rng.integers(3000, 31000, 15))
        # a tile at 2,048 product rows needs more than the tile budget
        sla._TILE_BYTES = 56 * 1024 * 1024
        for rows_product in (1024, 2048):
            for group in (128, 256, 512):
                sla._group_tokens = lambda window, g=group: g
                case("mixed step", (497, 11000, decode), rows_product,
                     group, window=WINDOW)
        sla._group_tokens = lambda window: 256
        case("decode-only step", (0, 0, decode), 1024, 256, window=WINDOW)
    return 0


if __name__ == "__main__":
    sys.exit(main())
