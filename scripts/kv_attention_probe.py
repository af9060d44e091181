"""Chip probe for the K/V attention call (``ops/pallas/ragged_paged_attention.py:
_ragged_kernel``) at the shapes of the three cells that run it, one
layer's call, ``--layers`` calls a timed run:

* ``--call mqa``: `ai21-jamba2-3b.agent-prefix-c64` (512 stream rows, 64
  slots, 20 query heads on ONE folded K/V head of 128, blocks of 64):
  64 decode rows over 8.3k-11.4k keys, and 63 of them beside a 449-row
  chunk that continues a prompt past its 8,192-token prefix;
* ``--call gqa``: `internlm2-1.8b.chat-c16` (16 slots, 16 query heads on 8
  K/V heads, a 4-D cache of blocks of 16): 16 decode rows, and 15 beside a
  497-row chunk;
* ``--call hybrid``: `phi4-mini-flash.reason-c32` (32 slots, 40 padded
  query heads on 10 K/V pairs, a FOLDED cache of blocks of 16): the
  512-key window call on 31 decode rows beside a 481-row chunk, and the
  read-only cross call of 32 rows over the full pool.

    env PYTHONPATH=. python scripts/kv_attention_probe.py --call mqa

With ``PYTHONPATH`` at another checkout it times that checkout's body.
``--sweep`` also times the body at other tile and page-group sizes (where
the module has ``_PRODUCT_ROWS`` and ``_GROUP_TOKENS``). Needs a TPU
(exits 2 without one: a CPU time is no device time). Prints one JSON line a
case: median, fastest and slowest of ``--reps`` timed runs in ms A CALL,
each closed by ``block_until_ready``, the (row, key) pairs the rows see,
and the largest difference from the ``jnp`` reference on the same inputs
relative to the reference's largest value.
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SHAPES = {   # stream rows, slots, block size, blocks a slot, pool, heads
    "mqa": dict(t=512, s=64, bs=64, mb=192, nb=12288, heads=20, kv=1,
                folded=True),
    "gqa": dict(t=512, s=16, bs=16, mb=128, nb=2048, heads=16, kv=8,
                folded=False),
    "hybrid": dict(t=512, s=32, bs=16, mb=256, nb=2112, heads=40, kv=10,
                   folded=True),
}
D = 128


def timed(fn, *args, reps):
    ms = []
    for i in range(reps + 2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        if i >= 2:                               # compile, then one warm
            ms.append((time.perf_counter() - t0) * 1e3)
    ms.sort()
    return out, dict(ms_median=ms[len(ms) // 2], ms_min=ms[0],
                     ms_max=ms[-1])


def stream(s, chunk, chunk_ctx, decode_ctx):
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
    ap.add_argument("--call", choices=sorted(SHAPES), default="mqa")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweep", action="store_true")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 2
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

    z = SHAPES[a.call]
    t, s, bs, mb, nb = z["t"], z["s"], z["bs"], z["mb"], z["nb"]
    h, kh = z["heads"], z["kv"]
    rng = np.random.default_rng(a.seed)
    bt = rng.integers(0, nb, (s, mb)).astype(np.int32)
    ks = jax.random.split(jax.random.key(a.seed), 3)
    page = (nb, bs, kh * D) if z["folded"] else (nb, bs, kh, D)
    q = jax.random.normal(ks[0], (a.layers, t, h, D), jnp.bfloat16)
    kc = jax.random.normal(ks[1], page, jnp.bfloat16)
    vc = jax.random.normal(ks[2], page, jnp.bfloat16)
    print(json.dumps(dict(device=jax.devices()[0].device_kind, call=a.call,
                          module=rpa.__file__)), flush=True)

    def case(name, rows, window=None, rows_t=t, **sizes):
        for k, v in sizes.items():
            setattr(rpa, k, v)
        cu, ctx, ns = (jnp.asarray(x) for x in stream(s, *rows))
        win = {} if window is None else {"window": window}

        def one(qi):
            return rpa._ragged_attend_pallas.__wrapped__(
                qi, kc, vc, jnp.asarray(bt), cu, ctx, ns, 0.088,
                interpret=False, **win)

        # a jit of its own a case: the module's sizes are read at trace
        fn = jax.jit(lambda qs: jax.lax.map(one, qs))
        qs = q[:, :rows_t]
        said = dict(case=name, window=window, **sizes)
        try:
            out, ms = timed(fn, qs, reps=a.reps)
        except Exception as e:          # a tiling the compiler refuses
            print(json.dumps(dict(said, refused=str(e)[-300:])), flush=True)
            return
        seg, pos, valid = rpa._token_layout(rows_t, s, cu, ctx, ns)
        # the reference holds every row's whole context: 64 rows of it
        pick = jnp.arange(0, rows_t, max(1, rows_t // 64))
        ref = jax.jit(lambda qi: rpa._ragged_attend_ref(
            qi, kc, vc, jnp.asarray(bt), ctx, seg[pick], pos[pick],
            valid[pick], 0.088, **win))(qs[0][pick])
        err = jnp.max(jnp.abs(out[0][pick].astype(jnp.float32)
                              - ref.astype(jnp.float32)))
        live = np.asarray(pos)[np.asarray(pos) >= 0]
        seen = np.minimum(live + 1, window) if window else live + 1
        print(json.dumps(dict(
            said, **{k: round(v / a.layers, 4) for k, v in ms.items()},
            pairs_seen=int(seen.sum()),
            rel_err=float(err / jnp.max(jnp.abs(ref.astype(jnp.float32)))),
            finite=bool(jnp.isfinite(out.astype(jnp.float32)).all()))),
            flush=True)

    if a.call == "mqa":
        decode = list(rng.integers(8300, 11400, 64))
        kinds = (("decode-only step", (0, 0, decode)),
                 ("mixed step", (449, 8192 + 449 + 64, decode[:63])))
    elif a.call == "gqa":
        decode = list(rng.integers(100, 1700, 16))
        kinds = (("decode-only step", (0, 0, decode)),
                 ("mixed step", (497, 900, decode[:15])))
    else:
        decode = list(rng.integers(100, 3000, 32))
        kinds = (("window mixed step", (481, 1200, decode[:31])),)
    window = 512 if a.call == "hybrid" else None
    for name, rows in kinds:
        case(name, rows, window)
    if a.call == "hybrid":
        case("cross read-only rows", (0, 0, decode), rows_t=32)
    if a.sweep and hasattr(rpa, "_PRODUCT_ROWS"):
        for rows_product, group in ((1024, 512), (2048, 256), (2048, 1024),
                                    (4096, 512)):
            for name, rows in kinds:
                case(name, rows, window, _PRODUCT_ROWS=rows_product,
                     _GROUP_TOKENS=group)
    return 0


if __name__ == "__main__":
    sys.exit(main())
