"""Chip probe for the ragged selective scan at its two cells' shapes
(T 512, E 5,120, N 16; 64 + 1 slots, `ai21-jamba2-3b.agent-prefix-c64`;
32 + 1, `phi4-mini-flash.reason-c32`): ``--layers`` calls a timed run (a
step's worth, each layer on its own donated state), the XLA loops beside
the Pallas kernel at several token tiles, on three steps: every slot a
decode row, the decode rows beside one chunk, the chunk alone (``--also``: more mixes, as decode rows+chunk rows).

    chiprun -- env PYTHONPATH=. python scripts/selective_scan_probe.py

Needs a TPU (exits 2 without one: a CPU time is no device time). Prints
one JSON line a case: median, fastest and slowest of ``--reps`` timed
runs in ms A CALL, each closed by ``block_until_ready``, and the kernel's
largest difference from the XLA route on the live rows and slots.
"""
import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas.selective_scan import selective_scan_pallas
from paddle_tpu.ops.selective_scan import ragged_selective_scan

T, E, N = 512, 5120, 16


def timed(fn, states, *args, reps):
    ms = []
    for i in range(reps + 2):
        t0 = time.perf_counter()
        out, states = jax.block_until_ready(fn(states, *args))
        if i >= 2:                               # compile, then one warm
            ms.append((time.perf_counter() - t0) * 1e3)
    ms.sort()
    return out, states, dict(ms_median=ms[len(ms) // 2], ms_min=ms[0],
                             ms_max=ms[-1])


def step(rows, slots, chunk):
    """``rows`` decode rows of one token, then one chunk row of ``chunk``
    tokens 2,048 tokens into its prompt."""
    nq = [1] * rows + ([chunk] if chunk else [])
    cu = np.zeros(slots + 1, np.int32)
    cu[1:len(nq) + 1] = np.cumsum(nq)
    cu[len(nq) + 1:] = cu[len(nq)]
    ctx = np.zeros(slots, np.int32)
    ctx[:len(nq)] = [300 + i for i in range(rows)] + (
        [2048 + chunk] if chunk else [])
    return cu, ctx, np.int32(len(nq))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--layers", type=int, default=26)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--block-t", default="128")
    ap.add_argument("--also", default="",
                    help="more steps, decode rows+chunk rows: 16+0,0+128")
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU: a CPU time is no device time", file=sys.stderr)
        return 2
    s = a.slots
    rng = np.random.default_rng(a.seed)
    x = jnp.asarray(rng.standard_normal((T, E)), jnp.bfloat16)
    dt = jnp.asarray(np.abs(rng.standard_normal((T, E))) * 0.1, jnp.float32)
    amat = jnp.asarray(-np.exp(rng.standard_normal((E, N)) * 0.5),
                       jnp.float32)
    b = jnp.asarray(rng.standard_normal((T, N)), jnp.bfloat16)
    c = jnp.asarray(rng.standard_normal((T, N)), jnp.bfloat16)
    slots = jnp.asarray(rng.permutation(s), jnp.int32)

    def fresh():
        return [jnp.asarray(rng.standard_normal((s + 1, N, E)), jnp.float32)
                for _ in range(a.layers)]

    def run(scan):
        @functools.partial(jax.jit, donate_argnums=0)
        def fn(states, cu, ctx, ns):
            ys, out = 0.0, []
            for st in states:
                y, st = scan(x, dt, b, c, st, cu, ctx, ns)
                # a corner keeps every layer's y alive at no traffic
                ys, out = ys + y[:, :128], out + [st]
            return ys, out
        return fn

    def xla(x, dt, b, c, st, cu, ctx, ns):
        return ragged_selective_scan(x, dt, amat, b, c, st, slots, cu, ctx,
                                     ns, impl="xla")

    routes = {"xla": run(xla)}
    for bt in (int(v) for v in a.block_t.split(",")):
        def kernel(x, dt, b, c, st, cu, ctx, ns, bt=bt):
            with jax.named_scope("ssm_scan"):
                return selective_scan_pallas(
                    x, dt, jnp.transpose(amat), b, c, st, slots, cu, ctx,
                    ns, block_t=bt)
        routes[f"pallas_{bt}"] = run(kernel)

    steps = {"decode": step(s, s, 0), "mixed": step(s - 1, s, T - s + 1),
             "chunk": step(0, s, T - s)}
    for part in a.also.split(","):
        if part:
            rows, chunk = (int(v) for v in part.split("+"))
            steps[part] = step(rows, s, chunk)
    for name, (cu, ctx, ns) in steps.items():
        live = int(cu[int(ns)])
        seen = np.asarray(slots)[:int(ns)]
        want = None
        keep = np.asarray(fresh()[0])
        for route, fn in routes.items():
            _, _, ms = timed(fn, fresh(), jnp.asarray(cu),
                             jnp.asarray(ctx), ns, reps=a.reps)
            # one call on a known state, for the comparison
            y1, st1 = jax.block_until_ready(fn(
                [jnp.array(keep) for _ in range(a.layers)], jnp.asarray(cu),
                jnp.asarray(ctx), ns))
            got = (np.asarray(y1)[:live] / a.layers, np.asarray(st1[0]))
            line = dict(step=name, route=route, rows=int(ns), tokens=live,
                        layers=a.layers, slots=s,
                        **{k: round(v / a.layers, 4) for k, v in ms.items()})
            if want is None:
                want = got
            else:
                line["y_diff"] = float(np.abs(got[0] - want[0]).max())
                line["state_diff"] = float(
                    np.abs(got[1][seen] - want[1][seen]).max())
                rest = [i for i in range(s) if i not in set(seen.tolist())]
                line["untouched_equal"] = bool(
                    (got[1][rest] == keep[rest]).all())
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
