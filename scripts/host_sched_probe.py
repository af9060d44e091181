"""Host probe for the scheduler's and the block manager's per-step work at
the 64-row prefix cell's shapes (``block_size`` 64, prompts of one
8,192-token prefix + ~700 own tokens): ``schedule()`` with 16, 32 and 64
decode rows, and ``match_prefix``, a request's first ``commit_prefix``
(from a fresh cursor, over the shared prefix) and ``prefix_digest`` after
a registration, with 1,128, 3,128 and 6,128 entries in the trie.

    PYTHONPATH=. python scripts/host_sched_probe.py

Runs on the host alone (no JAX array is built): what it prints is this
machine's CPU time, not a chip's step. Set ``PYTHONPATH`` to another
checkout to time that checkout's code. Prints one JSON line a case:
median, fastest and slowest of ``--reps`` timed calls in ms.
"""
import argparse
import json
import time

import numpy as np

from paddle_tpu.serving.block_manager import BlockManager
from paddle_tpu.serving.request import Request, RequestStatus, SamplingParams
from paddle_tpu.serving.scheduler import Scheduler, SchedulerConfig

BS = 64
PREFIX = 8192
OWN = 700
VOCAB = 65536


def stats(ms):
    ms = sorted(ms)
    return dict(ms_median=round(ms[len(ms) // 2], 4),
                ms_min=round(ms[0], 4), ms_max=round(ms[-1], 4))


def timed(fn, reps, setup=None):
    ms = []
    for _ in range(reps):
        if setup is not None:
            setup()
        t0 = time.perf_counter()
        fn()
        ms.append((time.perf_counter() - t0) * 1e3)
    return stats(ms)


def schedule_case(rows, reps, rng, prefix):
    """``rows`` requests decoding beside each other (tables allocated on
    the shared prefix, one token generated): the steady 64-row step."""
    bm = BlockManager(rows * 160, BS, enable_prefix_cache=True)
    sched = Scheduler(bm, SchedulerConfig(max_num_seqs=rows,
                                          max_batched_tokens=512))
    for i in range(rows):
        prompt = prefix + [int(t) for t in rng.integers(0, VOCAB, OWN)]
        req = Request(f"r{i}", prompt, sampling=SamplingParams(
            max_new_tokens=10 ** 6), arrival_time=float(i))
        bm.allocate(req.request_id, len(prompt) + 1, tokens=prompt)
        bm.commit_prefix(req.request_id, prompt, len(prompt))
        req.num_cached = len(prompt)
        req.append_token(1)
        req.status = RequestStatus.RUNNING
        sched.running.append(req)

    def advance(batch):
        for r, n in zip(batch.requests, batch.num_scheduled):
            r.num_cached += n
            r.append_token(1)

    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        batch = sched.schedule()
        ms.append((time.perf_counter() - t0) * 1e3)
        assert len(batch.requests) == rows and not batch.preempted
        advance(batch)
    return dict(case="schedule", rows=rows, **stats(ms))


def trie_cases(entries, reps, rng, prefix):
    """A trie of the shared prefix's 128 blocks and ~10 own blocks each
    of (entries - 128) / 10 finished requests (cached-free)."""
    bm = BlockManager(entries + 2048, BS, enable_prefix_cache=True)
    k = 0

    def serve_one():
        nonlocal k
        prompt = prefix + [int(t) for t in rng.integers(0, VOCAB, 10 * BS)]
        rid = f"q{k}"
        k += 1
        bm.allocate(rid, len(prompt), tokens=prompt)
        bm.commit_prefix(rid, prompt, len(prompt))
        bm.free(rid)

    while bm.prefix_digest()["n"] < entries:
        serve_one()
    out = []

    def fresh():
        return prefix + [int(t) for t in rng.integers(0, VOCAB, OWN)]

    prompts = [fresh() for _ in range(reps)]
    it = iter(prompts)
    out.append(dict(case="match_prefix", entries=entries,
                    **timed(lambda: bm.match_prefix(next(it)), reps)))
    # a request admitted on the prefix commits its first chunk from a
    # fresh cursor: the 128 shared blocks and the chunk's own
    state = {}

    def admit():
        if state:
            bm.free("c")
        state["p"] = fresh()
        bm.allocate("c", PREFIX + 448, tokens=state["p"])

    def commit():
        bm.commit_prefix("c", state["p"], PREFIX + 448)

    out.append(dict(case="first_commit_prefix", entries=entries,
                    **timed(commit, reps, setup=admit)))
    bm.free("c")
    # the heartbeat's call after a step that registered deep blocks
    before = getattr(bm, "num_digest_rebuilds", 0)
    out.append(dict(case="prefix_digest", entries=entries,
                    **timed(bm.prefix_digest, reps, setup=serve_one),
                    rebuilds=getattr(bm, "num_digest_rebuilds", 0) - before))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    prefix = [int(t) for t in rng.integers(0, VOCAB, PREFIX)]
    for rows in (16, 32, 64):
        print(json.dumps(schedule_case(rows, args.reps, rng, prefix)))
    for entries in (1128, 3128, 6128):
        for line in trie_cases(entries, args.reps, rng, prefix):
            print(json.dumps(line))


if __name__ == "__main__":
    main()
