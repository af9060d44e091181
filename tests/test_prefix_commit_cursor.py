"""The prefix trie commits each block once: ``BlockManager.commit_prefix``
resumes from a cursor kept per block table. The walk from block 0 that it
replaced is kept as the oracle (``tests/refs/prefix_commit_ref.py``): the
same calls must leave the same five trie maps, except where a key's first
owner was evicted under a living twin (stated and pinned below)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import Profiler
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.block_manager import (BlockManager,
                                              prefix_chain_hashes)
from refs.prefix_commit_ref import OracleBlockManager, trie_maps

BS = 4


def _pair(num_blocks=64, **kw):
    kw.setdefault("enable_prefix_cache", True)
    return (BlockManager(num_blocks, BS, **kw),
            OracleBlockManager(num_blocks, BS, **kw))


def _toks(rng, n, lo=0, hi=50):
    return [int(t) for t in rng.integers(lo, hi, size=n)]


# -- (a) the same maps as the walk from zero --------------------------------
@pytest.mark.parametrize("seed", range(8))
def test_trie_maps_equal_the_walk_from_zero_over_random_traces(seed):
    """Admissions behind shared stems, chunked commits, decode growth,
    finishes (some committing prompt + generated, as a session's capture
    does), aborts and preempt-and-recompute under the same id; the pool
    is large enough that nothing registered is ever re-claimed."""
    rng = np.random.default_rng(seed)
    bm, ref = _pair(num_blocks=4096)
    stems = [_toks(rng, 24) for _ in range(3)]
    sent = []
    live, preempted = {}, {}

    def prompt():
        if sent and rng.random() < 0.3:       # a twin of an earlier one
            return list(sent[int(rng.integers(0, len(sent)))])
        stem = stems[int(rng.integers(0, 3))]
        k = (24, 16, 12, 8, 0)[int(rng.integers(0, 5))]
        sent.append(stem[:k] + _toks(rng, int(rng.integers(2, 26)), 50, 90))
        return list(sent[-1])

    def both(call):
        got, want = call(bm), call(ref)
        assert got == want
        assert trie_maps(bm) == trie_maps(ref)
        return got

    def admit(rid, tokens):
        eff = min(both(lambda m: m.match_prefix(tokens)), len(tokens) - 1)
        n = int(rng.integers(1, len(tokens) - eff + 1))
        both(lambda m: m.allocate(rid, eff + n, tokens=tokens))
        covered = bm.last_hit_tokens + n
        assert ref.last_hit_tokens == bm.last_hit_tokens
        live[rid] = {"prompt": tokens, "tokens": list(tokens),
                     "covered": covered}
        both(lambda m: m.commit_prefix(rid, tokens, covered))

    for it in range(400):
        op = int(rng.integers(0, 6))
        if op == 0:
            admit(f"s{it}", prompt())
        elif op == 1 and preempted:       # recompute under the same id
            rid = list(preempted)[int(rng.integers(0, len(preempted)))]
            admit(rid, preempted.pop(rid))
        elif not live:
            continue
        else:
            rid = list(live)[int(rng.integers(0, len(live)))]
            st = live[rid]
            if op in (1, 2):              # a chunk, or one decoded token
                left = len(st["prompt"]) - st["covered"]
                n = int(rng.integers(1, left + 1)) if left > 0 else 1
                both(lambda m: m.append_slot(rid, st["covered"] + n,
                                             write_from=st["covered"]))
                st["covered"] += n
                if left <= 0:
                    st["tokens"].append(int(rng.integers(0, 90)))
                both(lambda m: m.commit_prefix(rid, st["prompt"],
                                               st["covered"]))
            elif op == 3:                 # finish; half capture the chain
                if rng.random() < 0.5:
                    both(lambda m: m.commit_prefix(
                        rid, list(st["tokens"]),
                        min(st["covered"], len(st["tokens"]))))
                both(lambda m: m.free(rid))
                live.pop(rid)
            elif op == 4:                 # abort
                both(lambda m: m.free(rid))
                live.pop(rid)
            else:                         # preempt: recompute later
                both(lambda m: m.free(rid))
                preempted[rid] = live.pop(rid)["prompt"]
        for m in (bm, ref):
            m.take_cow_pairs()
            m.check_invariants()
    # no eviction: every change of the trie was a registration
    assert bm._trie_rev == bm.num_prefix_blocks_committed > 50
    assert bm.num_prefix_hits > 0


# -- (b) the work is counted ------------------------------------------------
def test_each_full_prompt_block_is_visited_exactly_once():
    bm, _ = _pair()
    tokens = list(range(100, 130))          # 7 full blocks and a tail
    bm.allocate("r", 5, tokens=tokens)
    seen = []
    for covered in (5, 6, 8, 17, 17, 29, 30, 31, 40):
        bm.append_slot("r", covered, write_from=0)
        before = bm.num_commit_visited
        bm.commit_prefix("r", tokens, covered)
        seen.append(bm.num_commit_visited - before)
    assert seen == [1, 0, 1, 2, 0, 3, 0, 0, 0]
    assert bm.num_commit_visited == bm.num_prefix_blocks_committed == 7
    assert bm.match_prefix(tokens) == 28


def test_a_call_with_nothing_new_touches_nothing():
    """Decode steps: no slice, no key, no hash; the tokens are not even
    read."""
    bm, _ = _pair()
    tokens = list(range(12))
    bm.allocate("r", 12, tokens=tokens)
    bm.commit_prefix("r", tokens, 12)
    rev, cursor = bm._trie_rev, bm._commit_cursor["r"]

    class Unread(list):
        def __getitem__(self, i):
            raise AssertionError("a decode step's commit read the tokens")

    for covered in (12, 13, 20):
        bm.commit_prefix("r", Unread(tokens), covered)
    assert (bm._trie_rev, bm._commit_cursor["r"]) == (rev, cursor)
    assert bm.num_commit_visited == 3


def test_commit_without_a_table_or_without_the_cache_is_a_no_op():
    bm, _ = _pair()
    bm.commit_prefix("nobody", list(range(8)), 8)
    assert not bm._commit_cursor and bm.num_commit_visited == 0
    off = BlockManager(8, BS)
    off.allocate("r", 8)
    off.commit_prefix("r", list(range(8)), 8)
    assert not off._commit_cursor and not off._prefix_index
    off.check_invariants()


# -- (c) never discoverable early -------------------------------------------
@pytest.mark.parametrize("block", [0, 1, 2])
def test_a_block_is_not_matched_one_token_short_of_its_end(block):
    bm, _ = _pair()
    tokens = list(range(40, 40 + 3 * BS))
    bm.allocate("r", len(tokens), tokens=tokens)
    end = (block + 1) * BS
    bm.commit_prefix("r", tokens, end - 1)
    assert bm.match_prefix(tokens) == block * BS
    bm.commit_prefix("r", tokens, end)
    assert bm.match_prefix(tokens) == end


# -- (d) the cursor dies with the table -------------------------------------
def test_free_drops_the_cursor_and_a_recomputed_request_walks_from_zero():
    bm, ref = _pair(num_blocks=6)
    tokens = list(range(16))
    for m in (bm, ref):
        m.allocate("r", 16, tokens=tokens)
        m.commit_prefix("r", tokens, 16)
        m.free("r")                         # preempted
    assert "r" not in bm._commit_cursor
    bm.check_invariants()
    for m in (bm, ref):                     # the chain is evicted meanwhile
        m.allocate("other", 24)
        m.free("other")
        assert m.match_prefix(tokens) == 0
        m.allocate("r", 9, tokens=tokens)   # recompute, first chunk
        m.commit_prefix("r", tokens, 9)
    assert bm.match_prefix(tokens) == 8
    assert bm._commit_cursor["r"][0] == 2
    assert trie_maps(bm) == trie_maps(ref)
    bm.check_invariants()


def test_a_reused_request_id_starts_clean():
    bm, ref = _pair()
    first, second = list(range(12)), list(range(60, 72))
    for m in (bm, ref):
        m.allocate("r", 12, tokens=first)
        m.commit_prefix("r", first, 12)
        m.free("r")
        m.allocate("r", 12, tokens=second)
        m.commit_prefix("r", second, 8)
    assert bm.match_prefix(second) == 8 and bm.match_prefix(first) == 12
    assert trie_maps(bm) == trie_maps(ref)


def test_check_invariants_holds_the_cursor_to_its_table():
    bm, _ = _pair()
    tokens = list(range(12))
    bm.allocate("r", 12, tokens=tokens)
    bm.commit_prefix("r", tokens, 12)
    bm.check_invariants()
    idx, key, chash = bm._commit_cursor["r"]
    bm._commit_cursor["r"] = (idx + 1, key, chash)
    with pytest.raises(AssertionError, match="commit cursor of 'r' at"):
        bm.check_invariants()
    bm._commit_cursor["r"] = (idx, key, chash)
    bm._commit_cursor["ghost"] = (1, key, chash)
    with pytest.raises(AssertionError, match="outlived its block table"):
        bm.check_invariants()


# -- (e) swap out / in: the cursor is RESET ----------------------------------
@pytest.mark.parametrize("evicted_meanwhile", [False, True])
def test_swap_out_resets_the_cursor_and_swap_in_offers_the_new_blocks(
        evicted_meanwhile):
    """The swapped-in table is new blocks: they are offered to the trie
    from block 0, once. Where the old blocks are still registered
    (cached-free) nothing changes; where they were re-claimed meanwhile
    the new blocks take the keys, as the walk from zero had it."""
    bm, ref = _pair(num_blocks=8, num_host_blocks=8)
    tokens = list(range(16))
    for m in (bm, ref):
        m.allocate("r", 16, tokens=tokens)
        m.commit_prefix("r", tokens, 16)
        m.swap_out("r", 16)
        if evicted_meanwhile:
            m.allocate("other", 32)
            m.free("other")
            assert m.match_prefix(tokens) == 0
    assert "r" not in bm._commit_cursor
    bm.check_invariants()
    visited, committed = bm.num_commit_visited, bm.num_prefix_blocks_committed
    for m in (bm, ref):
        m.swap_in("r")
        m.commit_prefix("r", tokens, 16)
        m.commit_prefix("r", tokens, 17)
    assert bm.num_commit_visited - visited == 4
    assert bm.num_prefix_blocks_committed - committed == (
        4 if evicted_meanwhile else 0)
    assert bm.match_prefix(tokens) == 16
    assert trie_maps(bm) == trie_maps(ref)
    bm.check_invariants()


# -- (f) a finished session's chain: prompt, then prompt + generated ---------
def test_commit_continues_from_the_prompt_into_generated_blocks():
    bm, ref = _pair()
    prompt = list(range(10))
    full = prompt + list(range(200, 213))            # 23 tokens, 5 blocks
    for m in (bm, ref):
        m.allocate("r", 10, tokens=prompt)
        m.commit_prefix("r", prompt, 10)
        m.append_slot("r", 22, write_from=10)
    visited = bm.num_commit_visited
    for m in (bm, ref):
        m.commit_prefix("r", prompt, 22)             # decode steps: no-ops
        m.commit_prefix("r", full, 22)               # on_finish
    assert bm.num_commit_visited - visited == 3      # blocks 2, 3, 4 only
    hashes = prefix_chain_hashes(full, BS)
    assert [bm._hash_tokens[h] for h in hashes] == [4, 8, 12, 16, 20]
    tokens, blocks = bm.prefix_blocks_by_hash(hashes[-1])
    assert tokens == full[:20] and blocks == bm.block_table("r")[:5]
    assert bm.match_prefix(full) == 20
    assert trie_maps(bm) == trie_maps(ref)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 128)
    kw.setdefault("max_batched_tokens", 16)
    return LLMEngine(model, EngineConfig(block_size=BS, **kw))


def _drain(eng):
    steps = 0
    while eng.has_unfinished():
        eng.step()
        eng.block_manager.check_invariants()
        steps += 1
        assert steps < 500, "engine failed to converge"


def test_session_capture_names_the_chain_prefix_chain_hashes_names(
        tiny_model):
    """``kvtier.on_finish`` hands ``req.tokens`` (prompt and generated)
    for a request whose prompt is already under the cursor."""
    eng = _engine(tiny_model, kv_tiers=True)
    prompt = _toks(np.random.default_rng(5), 22, 0, 256)
    eng.add_request("s", prompt, sampling=SamplingParams(max_new_tokens=9))
    _drain(eng)
    bm = eng.block_manager
    rec = eng._kvtier.sessions["s"]
    assert rec.tokens[:22] == prompt and len(rec.tokens) == 31
    full = rec.covered // BS
    assert full == 7                       # 30 cached tokens: 5 + 2 blocks
    hashes = prefix_chain_hashes(rec.tokens, BS)[:full]
    assert rec.chain_hash == hashes[-1]
    assert all(h in bm._hash_key for h in hashes)
    assert bm.match_prefix(rec.tokens) == full * BS
    assert bm.num_commit_visited == full   # each block once, the prompt's
    # in engine.post, the generated ones at the finish
    assert eng.metrics.snapshot()["prefix_blocks_committed"] == full


# -- (g) the one behaviour that differs -------------------------------------
def test_a_twin_below_the_cursor_is_not_offered_again_after_eviction():
    """Two requests compute the same prompt side by side; the first to
    commit owns the keys, finishes, and its blocks are re-claimed. The
    walk from zero then registered the living twin's own copies on its
    next step; with a cursor they stay unregistered until it ends: a
    later request may miss a hit it could have had, never get a wrong
    one."""
    bm, ref = _pair(num_blocks=8)
    tokens = list(range(8))
    for m in (bm, ref):
        m.allocate("a", 8, tokens=tokens)
        m.allocate("b", 8, tokens=tokens)     # nothing committed yet: no hit
        m.commit_prefix("a", tokens, 8)
        m.commit_prefix("b", tokens, 8)       # keeps a's blocks
        assert m.match_prefix(tokens) == 8
        m.free("a")
        m.allocate("evictor", 24)             # re-claims a's cached blocks
        m.free("evictor")
        assert m.match_prefix(tokens) == 0
        m.append_slot("b", 9)
        m.commit_prefix("b", tokens, 9)       # b's next decode step
        m.check_invariants()
    assert ref.match_prefix(tokens) == 8      # the old walk: b re-offered
    assert bm.match_prefix(tokens) == 0       # the cursor: not offered
    assert bm.num_prefix_blocks_committed == 2
    # the next request with that prompt computes it and owns the keys
    bm.free("b")
    bm.allocate("c", 8, tokens=tokens)
    assert bm.last_hit_tokens == 0
    bm.commit_prefix("c", tokens, 8)
    assert bm.match_prefix(tokens) == 8
    bm.check_invariants()


def test_an_evicted_chain_stays_forgotten_under_a_running_request():
    """``evict_chain`` (a session offloaded to a peer: the remote copy is
    the authoritative one) is not undone by the owner's next step."""
    bm, _ = _pair()
    tokens = list(range(12))
    bm.allocate("r", 12, tokens=tokens)
    bm.commit_prefix("r", tokens, 12)
    assert bm.evict_chain(tokens, 12) == 3
    bm.append_slot("r", 13)
    bm.commit_prefix("r", tokens, 13)
    assert bm.match_prefix(tokens) == 0
    bm.check_invariants()


# -- (b), (h) the engine ----------------------------------------------------
def test_engine_post_reads_zero_commits_on_decode_only_steps(tiny_model):
    """``ptpu:engine.post`` carries what the step's commits walked and
    registered; over a run each prompt block is walked once."""
    eng = _engine(tiny_model)
    rng = np.random.default_rng(11)
    prompts = [_toks(rng, n, 0, 256) for n in (29, 6, 22, 3)]
    prof = Profiler(record_op_events=False).start()
    try:
        for i, p in enumerate(prompts):
            eng.add_request(f"r{i}", p,
                            sampling=SamplingParams(max_new_tokens=6))
        _drain(eng)
    finally:
        prof.stop()
    disp = [e["args"] for e in prof.host_events
            if e["name"] == "engine.dispatch"]
    post = [e["args"] for e in prof.host_events if e["name"] == "engine.post"]
    assert len(disp) == len(post) > 6
    decode_only = [p for d, p in zip(disp, post) if d["prefill_rows"] == 0]
    assert len(decode_only) >= 3
    assert all(p["commit_visited"] == 0 and p["committed_blocks"] == 0
               for p in decode_only)
    blocks = sum(len(p) // BS for p in prompts)
    assert eng.scheduler.num_preemptions == 0
    assert sum(p["commit_visited"] for p in post) == blocks
    assert sum(p["committed_blocks"] for p in post) == blocks
    snap = eng.metrics.snapshot()
    assert snap["prefix_blocks_visited"] == blocks
    assert snap["prefix_blocks_committed"] == blocks
    assert not eng.block_manager._commit_cursor    # all tables freed


def test_a_later_request_hits_the_shared_prefix_and_streams_are_unchanged(
        tiny_model):
    """Two requests behind a shared 64-token prefix, the second admitted
    after the first's prefill: it still hits, and both streams equal the
    streams of an engine without the prefix cache."""
    rng = np.random.default_rng(17)
    shared = _toks(rng, 64, 0, 256)
    prompts = [shared + _toks(rng, 7, 0, 256), shared + _toks(rng, 10, 0, 256)]
    sps = [SamplingParams(max_new_tokens=8),
           SamplingParams(max_new_tokens=8, temperature=0.8, top_k=20,
                          seed=5)]

    def serve(**kw):
        eng = _engine(tiny_model, **kw)
        eng.add_request("r0", prompts[0], sampling=sps[0])
        while eng.get_request("r0").num_cached < len(prompts[0]):
            eng.step()                     # the first's chunked prefill
        eng.add_request("r1", prompts[1], sampling=sps[1])
        eng.step()
        hit = eng.block_manager.num_prefix_hit_tokens
        _drain(eng)
        return eng, hit, [eng.get_request(r).generated for r in ("r0", "r1")]

    eng, hit, streams = serve()
    assert eng.cfg.prefix_cache and hit == 64
    assert eng.metrics.num_prompt_tokens == 71 + 10
    off, hit_off, streams_off = serve(prefix_cache=False)
    assert hit_off == 0 and off.metrics.num_prompt_tokens == 71 + 74
    assert streams == streams_off
    assert all(len(s) == 8 for s in streams)
