"""The scheduler's and the block manager's per-step host work is linear.

Requests compare by identity, so the scheduler's passes test membership in
sets; a trie look-up hashes and compares one block's tokens at any depth;
``prefix_digest`` keeps its order as entries come and go instead of sorting
the whole trie when it is asked. None of it may change a decision:
``tests/refs/serving_decisions.json`` is what a seeded storm through
``Scheduler`` + ``BlockManager`` (no model) decided, step by step, before
these three changes; the storm must decide the same today. Regenerate it
only from a tree whose decisions are known good:
``python tests/test_serving_host_linear.py --write``.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu.serving.block_manager import BlockManager
from paddle_tpu.serving.request import Request, SamplingParams
from paddle_tpu.serving.scheduler import Scheduler, SchedulerConfig

GOLDEN = os.path.join(os.path.dirname(__file__), "refs",
                      "serving_decisions.json")

# block size, device blocks, host slots (host swap), state snapshots, the
# scheduler's rows and token budget, the shared prefixes' lengths
CASES = {
    "bs16-snapshots": dict(bs=16, blocks=40, host=0, snaps=10, rows=10,
                           budget=96, prefixes=(80, 48)),
    "bs64": dict(bs=64, blocks=30, host=0, snaps=0, rows=12, budget=160,
                 prefixes=(256, 128)),
    "bs16-host-swap": dict(bs=16, blocks=36, host=48, snaps=0, rows=10,
                           budget=128, prefixes=(96, 64)),
}
STEPS = 150
VOCAB = 1000


class _NoCopy:
    """The engine's KV mover, without bytes: host swap moves nothing."""

    def copy_out(self, request, dev, host):
        pass

    def copy_in(self, request, host, dev):
        pass


def _short(obj) -> str:
    return hashlib.blake2b(json.dumps(obj).encode(),
                           digest_size=8).hexdigest()


def storm(name: str, steps: int = STEPS) -> list:
    """One seeded run of arrivals (shared prefixes, exact repeats, fresh
    prompts), admissions, chunks, decodes, preemption or swap under a
    small pool, finishes, aborts and evictions. Returns one record a
    step: the batch kind and its rows, the requests preempted and swapped
    in, what the trie did, and a hash of the tables, copies and digest."""
    c = CASES[name]
    rng = np.random.default_rng(20261015 + len(name))
    bs = c["bs"]
    bm = BlockManager(c["blocks"], bs, num_host_blocks=c["host"],
                      enable_prefix_cache=True,
                      state_slots=c["rows"] if c["snaps"] else 0,
                      state_snapshots=c["snaps"])
    sched = Scheduler(bm, SchedulerConfig(max_num_seqs=c["rows"],
                                          max_batched_tokens=c["budget"]),
                      swap_mode="host" if c["host"] else "recompute",
                      kv_swapper=_NoCopy() if c["host"] else None)
    prefixes = [[int(t) for t in rng.integers(0, VOCAB, n)]
                for n in c["prefixes"]]
    sent, out = [], []
    n = 0
    for step in range(steps):
        for _ in range(int(rng.integers(0, 3))):
            u = rng.random()
            if sent and u < 0.2:                     # an exact repeat
                prompt = list(sent[int(rng.integers(0, len(sent)))])
            else:
                p = prefixes[int(rng.integers(0, 2))] if u < 0.85 else []
                cut = len(p) if rng.random() < 0.7 else int(
                    rng.integers(0, len(p) + 1))
                prompt = p[:cut] + [int(t) for t in rng.integers(
                    0, VOCAB, int(rng.integers(1, 3 * bs)))]
            sent.append(prompt)
            req = Request(f"r{n}", prompt, sampling=SamplingParams(
                max_new_tokens=int(rng.integers(2, 3 * bs)),
                priority=int(rng.random() < 0.2)),
                arrival_time=float(n))
            sched.add(req)
            n += 1
        if sched.running and rng.random() < 0.04:
            victim = sched.running[int(rng.integers(0, len(sched.running)))]
            sched.abort(victim.request_id)
        batch = sched.schedule()
        rows = list(zip(batch.requests, batch.num_scheduled))
        if bm.state_snapshots:
            bm.plan_snapshots([(r.request_id, r.num_cached + k,
                                len(r.prompt_ids)) for r, k in rows])
        copies = bm.take_state_copies()
        cow = bm.take_cow_pairs()
        tables = [bm.block_table(r.request_id) for r, _ in rows]
        for r, k in rows:
            r.num_cached += k
            bm.commit_prefix(r.request_id, r.prompt_ids, r.num_cached)
            if r.num_cached < len(r.tokens):
                continue
            if r.append_token(int(rng.integers(0, VOCAB))):
                sched.finish(r)
        bm.check_invariants()
        digest = bm.prefix_digest()
        out.append({
            "kind": batch.kind,
            "rows": " ".join(f"{r.request_id}:{k}" for r, k in rows),
            "preempted": [r.request_id for r in batch.preempted],
            "swapped_in": [r.request_id for r in batch.swapped_in],
            "hit": bm.last_hit_tokens,
            "committed": bm.num_prefix_blocks_committed,
            "digest_n": digest["n"],
            "state": _short([tables, [list(p) for p in copies], cow,
                             list(digest["h"].items()),
                             bm.num_prefix_hits, bm.num_prefix_hit_tokens,
                             bm.num_snapshot_hits,
                             bm.num_snapshot_evictions,
                             sched.num_preemptions, sched.num_swap_outs,
                             bm.num_free_blocks]),
        })
    return out


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CASES))
def test_decisions_equal_the_recorded_ones(name, golden):
    got = storm(name)
    want = golden[name]
    assert len(got) == len(want)
    for step, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"{name}: step {step} decided otherwise"


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_storm_covers_what_it_claims(name, golden):
    """The record holds chunks beside decodes, hits, preemptions or
    swaps, and evictions that shrank the trie: a storm that stopped
    doing these would pass the equality above for nothing."""
    rec = golden[name]
    assert sum(s["kind"] == "mixed" for s in rec) > 20
    assert sum(bool(s["preempted"]) for s in rec) > 3
    if CASES[name]["host"]:
        assert sum(bool(s["swapped_in"]) for s in rec) > 3
    assert sum(s["hit"] > 0 for s in rec) > 50
    assert sum(a["digest_n"] > b["digest_n"]
               for a, b in zip(rec, rec[1:])) > 3
    assert rec[-1]["committed"] > max(s["digest_n"] for s in rec)


# -- requests compare by identity ------------------------------------------
def test_requests_are_equal_only_to_themselves():
    a = Request("r", [1, 2, 3], arrival_time=0.0)
    b = Request("r", [1, 2, 3], arrival_time=0.0)
    assert a == a and a != b and len({a, b, a}) == 2


def test_schedule_with_64_running_rows_compares_no_requests(monkeypatch):
    """Passes A and B test membership by identity: no ``__eq__`` runs,
    where a list walk ran one a row for every row."""
    bm = BlockManager(64 * 40, 16)
    sched = Scheduler(bm, SchedulerConfig(max_num_seqs=64,
                                          max_batched_tokens=512))
    rng = np.random.default_rng(7)
    for i in range(64):
        sched.add(Request(f"r{i}", [int(t) for t in rng.integers(
            0, VOCAB, 8 + i % 5)], arrival_time=float(i)))
    calls = []

    def eq(self, other):
        calls.append(1)
        return self is other

    for _ in range(3):                   # admit, then decode, then decode
        batch = sched.schedule()
        for r, n in zip(batch.requests, batch.num_scheduled):
            r.num_cached += n
            if r.num_cached == len(r.tokens):
                r.append_token(1)
        monkeypatch.setattr(Request, "__eq__", eq)
    assert batch.kind == "decode" and len(batch.requests) == 64
    assert calls == []


# -- a look-up hashes and compares one block --------------------------------
class _Token(int):
    """A token that counts how often it is hashed or compared."""

    seen = [0]

    def __hash__(self):
        self.seen[0] += 1
        return int.__hash__(self)

    def __eq__(self, other):
        self.seen[0] += 1
        return int.__eq__(self, other)


@pytest.mark.parametrize("bs", [16, 64])
def test_a_walk_140_blocks_deep_touches_each_token_a_few_times(bs):
    """Walking, committing from a fresh cursor and resolving a chain hash
    140 blocks deep hash and compare each token twice (once hashed, once
    compared); keys hashed from the root made it 141 times a token."""
    bm = BlockManager(400, bs, enable_prefix_cache=True)
    tokens = list(range(140 * bs))
    bm.allocate("a", len(tokens), tokens=tokens)
    bm.commit_prefix("a", tokens, len(tokens))
    probe = [_Token(t) for t in tokens]          # objects the trie never saw
    _Token.seen[0] = 0
    assert bm.match_prefix(probe) == len(tokens)
    assert _Token.seen[0] <= 3 * len(tokens)
    bm.allocate("b", len(tokens), tokens=tokens)
    _Token.seen[0] = 0
    bm.commit_prefix("b", probe, len(tokens))   # every block held by "a"
    assert _Token.seen[0] <= 3 * len(tokens)
    assert bm.num_prefix_blocks_committed == 140
    deepest = bm.prefix_digest(max_entries=1000)
    chash = max(deepest["h"], key=deepest["h"].get)
    got, blocks = bm.prefix_blocks_by_hash(chash)
    assert got == tokens and blocks == bm.block_table("a")
    bm.take_cow_pairs()                         # b's capped write
    bm.check_invariants()


def test_a_child_of_a_dropped_and_recommitted_link_is_found_again():
    """Keys are token content: when a chain's first block is evicted and
    the same tokens are committed again by another request, the deeper
    blocks that stayed registered are reachable again (as they were with
    nested tuples), and the new request's copies are not registered."""
    bm = BlockManager(8, 4, enable_prefix_cache=True)
    tokens = list(range(12))
    bm.allocate("a", 12, tokens=tokens)
    bm.commit_prefix("a", tokens, 12)
    _, second, third = bm.block_table("a")
    bm.free("a")                          # cached-free: first is evicted first
    bm.allocate("x", 4 * 6)               # the 5 uncached, then `first`
    assert bm.match_prefix(tokens) == 0
    bm.free("x")
    bm.allocate("b", 12, tokens=tokens)
    assert bm.last_hit_tokens == 0
    bm.commit_prefix("b", tokens, 12)     # block 0 is b's; 1 and 2 are a's
    assert bm.num_prefix_blocks_committed == 4
    b0 = bm.block_table("b")[0]
    bm.free("b")
    assert bm._matched_chain(tokens, 12)[0] == [b0, second, third]
    bm.check_invariants()


# -- the digest keeps its order ---------------------------------------------
def _old_digest(bm, max_entries):
    items = sorted(bm._hash_tokens.items(), key=lambda kv: (kv[1], kv[0]))
    return {"bs": bm.block_size, "n": len(items),
            "h": dict(items[:max_entries])}


@pytest.mark.parametrize("max_entries", [16, 128])
def test_digest_equals_the_full_sort_after_every_change(max_entries):
    """1,000 random commits, finishes and evictions; after each, the
    digest (content AND order of ``"h"``) is the full sort's, and it was
    rebuilt only when an entry entered or left the kept part."""
    rng = np.random.default_rng(max_entries)
    bm = BlockManager(160, 4, enable_prefix_cache=True)
    stems = [[int(t) for t in rng.integers(0, 50, 24)] for _ in range(4)]
    live = []
    kept = None
    rebuilt = 0
    for i in range(1000):
        if live and (rng.random() < 0.45 or bm.num_free_blocks < 12):
            bm.free(live.pop(int(rng.integers(0, len(live)))))
        else:
            stem = stems[int(rng.integers(0, 4))]
            p = stem[:int(rng.integers(0, 25))] + [
                int(t) for t in rng.integers(0, 99, int(rng.integers(1, 20)))]
            rid = f"r{i}"
            bm.allocate(rid, len(p), tokens=p)
            bm.commit_prefix(rid, p, int(rng.integers(1, len(p) + 1)))
            live.append(rid)
        before = bm.num_digest_rebuilds
        got = bm.prefix_digest(max_entries)
        want = _old_digest(bm, max_entries)
        assert got == want and list(got["h"]) == list(want["h"])
        if bm.num_digest_rebuilds > before:
            rebuilt += 1
            assert list(want["h"].items()) != kept
        else:
            assert list(want["h"].items()) == kept
        kept = list(want["h"].items())
        assert bm.prefix_digest(max_entries) is got
    bm.check_invariants()
    assert 0 < rebuilt < 1000


def test_snapshot_reports_the_digest_rebuilds():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import EngineConfig, LLMEngine

    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    eng = LLMEngine(model, EngineConfig(block_size=4, max_num_seqs=2,
                                        max_model_len=64,
                                        max_batched_tokens=16))
    shared = list(range(20))
    for i in range(3):
        eng.add_request(f"r{i}", shared + [100 + i],
                        sampling=SamplingParams(max_new_tokens=3))
    while eng.has_unfinished():
        eng.step()
        eng.prefix_digest()               # the router's heartbeat
    snap = eng.metrics.snapshot()
    assert snap["prefix_digest_rebuilds"] == \
        eng.block_manager.num_digest_rebuilds
    # while the trie holds fewer than 128 entries every registration
    # lands in the kept part: a rebuild on each step that committed a
    # block, none on the decode steps
    assert 1 <= snap["prefix_digest_rebuilds"] < eng.metrics.engine_steps


def write_golden(record: dict):
    """One step a line, so that a diff of the record reads by step."""
    with open(GOLDEN, "w") as f:
        f.write("{\n" + ",\n".join(
            json.dumps(name) + ": [\n" + ",\n".join(
                json.dumps(step, sort_keys=True) for step in steps) + "\n]"
            for name, steps in sorted(record.items())) + "\n}\n")


if __name__ == "__main__" and sys.argv[1:] == ["--write"]:
    write_golden({name: storm(name) for name in CASES})
