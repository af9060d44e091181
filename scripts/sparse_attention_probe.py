"""Chip probe for a full layer's selected attention at the
`dots3-note-prev-d5.longdoc-c16` shape (512 stream rows, 16 slots of
32,768 tokens, 128 heads of 640 lanes, top-2,048): times the kernel of
``ops/pallas/sparse_latent_attention.py`` on mixed steps whose chunk sits
at several depths of its prompt and on a decode-only step, for several
(stream rows a tile, tokens a page group).

    chiprun -- env PYTHONPATH=. python scripts/sparse_attention_probe.py

Needs a TPU (exits 2 without one: a CPU time is no device time). Prints
one JSON line a case: median, fastest and slowest of ``--reps`` timed
calls in ms, each closed by ``block_until_ready``, and the (row, key)
pairs the rows see and select.
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

T, S, MB, NB, BS = 512, 16, 2048, 32768, 16
HEADS, LANES, V_LANES, TOP = 128, 640, 512, 2048


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


def stream(rng, chunk_ctx):
    """(cu, ctx, ns): 15 decode rows at 3k-31k and, with ``chunk_ctx``,
    a 497-row chunk that ends at that position."""
    n_dec = 15
    chunk = T - n_dec if chunk_ctx else 0
    lens = ([chunk] if chunk else []) + [1] * n_dec
    cu = np.concatenate([[0], np.cumsum(lens)])
    cu = np.concatenate([cu, np.full(S + 1 - len(cu), cu[-1])])
    ctx = np.zeros((S,), np.int32)
    ctx[:len(lens)] = ([chunk_ctx] if chunk else []) + list(
        rng.integers(3000, 31000, n_dec))
    return cu.astype(np.int32), ctx, np.int32(len(lens))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 2
    from paddle_tpu.ops import sparse_index
    from paddle_tpu.ops.pallas import sparse_latent_attention as sla

    rng = np.random.default_rng(a.seed)
    bt = jnp.asarray(rng.permutation(NB).astype(np.int32).reshape(S, MB))
    k1, k2, k3 = jax.random.split(jax.random.key(a.seed), 3)
    q = jax.random.normal(k1, (T, HEADS, LANES), jnp.bfloat16)
    pool = jax.random.normal(k2, (NB, BS, LANES), jnp.bfloat16)
    at = jnp.arange(MB * BS, dtype=jnp.int32)[None, :]
    noise = jax.random.normal(k3, (T, MB * BS), jnp.float32)
    select = jax.jit(lambda s: sparse_index.select_topk(s, TOP))
    print(json.dumps(dict(device=jax.devices()[0].device_kind)), flush=True)

    def case(name, chunk_ctx, tile, group):
        sla._TILE_ROWS, sla._GROUP_TOKENS = tile, group
        cu, ctx, ns = (jnp.asarray(x) for x in stream(
            np.random.default_rng(a.seed), chunk_ctx))
        _, pos, _ = sla._token_layout(T, S, cu, ctx, ns)
        mask = select(jnp.where(at <= pos[:, None], noise, -jnp.inf))
        # a jit of its own a case: the constants above are read at trace
        fn = jax.jit(lambda *x: sla._attend_pallas.__wrapped__(
            *x, scale=0.07, v_lanes=V_LANES, interpret=False))
        try:
            out, ms = timed(fn, q, pool, mask, bt, cu, ctx, ns, reps=a.reps)
        except Exception as e:          # a tiling the compiler refuses
            print(json.dumps(dict(case=name, tile_rows=tile,
                                  group_tokens=group,
                                  refused=str(e)[:300])), flush=True)
            return
        live = np.asarray(pos) >= 0
        print(json.dumps(dict(
            case=name, tile_rows=tile, group_tokens=group,
            chunk_ctx=chunk_ctx, **ms,
            pairs_seen=int((np.asarray(pos)[live] + 1).sum()),
            pairs_selected=int(jnp.sum(mask)),
            finite=bool(jnp.isfinite(out.astype(jnp.float32)).all()))),
            flush=True)

    for tile, group in ((8, 512), (16, 512), (8, 1024), (16, 1024),
                        (8, 256)):
        case("mixed step", 11000, tile, group)
    tile, group = 8, 512
    for chunk_ctx in (1500, 4000, 7000, 20000, 30000):
        case("mixed step", chunk_ctx, tile, group)
    case("decode-only step", 0, tile, group)
    case("decode-only step", 0, 16, 1024)
    return 0


if __name__ == "__main__":
    sys.exit(main())
