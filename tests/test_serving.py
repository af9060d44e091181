"""serving.BlockManager / Scheduler invariants (model-free fast tests).

Pins the allocator + scheduler contracts: exact free-block accounting
under randomized admit/decode/free/preempt sequences, no double
allocation, the mixed batch's order and raw budget, preempted requests
re-admit and finish, and the FCFS starvation guard (waiting requests
eventually run)."""
import numpy as np
import pytest

from paddle_tpu.serving import (
    BlockManager, NoFreeBlocksError, Request, RequestStatus,
    SamplingParams, Scheduler, SchedulerConfig,
)


def _req(rid, n_prompt, max_new=4, arrival=None):
    r = Request(request_id=str(rid), prompt_ids=list(range(1, n_prompt + 1)),
                sampling=SamplingParams(max_new_tokens=max_new))
    if arrival is not None:
        r.arrival_time = arrival
    return r


# ---------------------------------------------------------------------------
# BlockManager
# ---------------------------------------------------------------------------
def test_block_manager_allocate_append_free_accounting():
    bm = BlockManager(num_blocks=8, block_size=4)
    t = bm.allocate("a", 10)             # 3 blocks
    assert len(t) == 3 and bm.num_free_blocks == 5
    # growth inside the last block costs nothing
    assert bm.append_slot("a", 11) == t and bm.num_free_blocks == 5
    assert bm.append_slot("a", 12) == t
    # crossing a block boundary claims exactly one
    t2 = bm.append_slot("a", 13)
    assert len(t2) == 4 and bm.num_free_blocks == 4
    assert bm.free("a") == 4
    assert bm.num_free_blocks == 8
    assert bm.free("a") == 0             # idempotent
    bm.check_invariants()


def test_block_manager_rejects_double_allocation():
    bm = BlockManager(num_blocks=4, block_size=4)
    bm.allocate("a", 4)
    with pytest.raises(ValueError, match="already holds"):
        bm.allocate("a", 4)


def test_block_manager_oom_signals():
    bm = BlockManager(num_blocks=2, block_size=4)
    bm.allocate("a", 8)
    assert not bm.can_allocate(1)
    with pytest.raises(NoFreeBlocksError):
        bm.allocate("b", 1)
    with pytest.raises(NoFreeBlocksError):
        bm.append_slot("a", 9)
    bm.check_invariants()


def test_block_manager_randomized_invariants():
    """Randomized admit/grow/free/preempt storm; the exact-accounting
    invariants must hold after EVERY operation."""
    rng = np.random.default_rng(0)
    bm = BlockManager(num_blocks=16, block_size=4)
    lens = {}
    for step in range(2000):
        op = rng.integers(0, 3)
        if op == 0:  # admit
            rid = f"r{step}"
            n = int(rng.integers(1, 20))
            if bm.can_allocate(n):
                bm.allocate(rid, n)
                lens[rid] = n
            else:
                with pytest.raises(NoFreeBlocksError):
                    bm.allocate(rid, n)
        elif op == 1 and lens:  # grow (a decode slot)
            rid = list(lens)[int(rng.integers(0, len(lens)))]
            new_len = lens[rid] + 1
            if bm.can_append(rid, new_len):
                bm.append_slot(rid, new_len)
                lens[rid] = new_len
            else:
                with pytest.raises(NoFreeBlocksError):
                    bm.append_slot(rid, new_len)
        elif op == 2 and lens:  # free (finish OR preempt-reclaim)
            rid = list(lens)[int(rng.integers(0, len(lens)))]
            got = bm.free(rid)
            assert got == bm.blocks_needed(lens.pop(rid))
        bm.check_invariants()
    for rid in list(lens):
        bm.free(rid)
    assert bm.num_free_blocks == 16


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------
def _is_decode_row(r):
    return r.num_generated > 0 and len(r.tokens) - r.num_cached == 1


def _run_batch(sched, batch, token=lambda: 7):
    """What the engine does with a scheduled batch, model-free: every
    row advances by the tokens it was scheduled, and a row that reached
    its last token gets one sampled. Holds every batch to the mixed
    policy's contract on the way: one count per row, the raw budget and
    the seats never exceeded, decode rows ahead of prefill chunks, the
    kind naming what the batch holds."""
    cfg = sched.config
    assert len(batch.num_scheduled) == len(batch.requests)
    assert sum(batch.num_scheduled) <= cfg.max_batched_tokens
    assert len(batch.requests) <= cfg.max_num_seqs
    assert len(sched.running) <= cfg.max_num_seqs
    decode = [_is_decode_row(r) for r in batch.requests]
    assert decode == sorted(decode, reverse=True), "decode rows go first"
    want = ("idle" if not decode else "decode" if all(decode)
            else "mixed" if any(decode) else "prefill")
    assert batch.kind == want
    for r, n in zip(batch.requests, batch.num_scheduled):
        d = len(r.draft_tokens)
        assert 1 <= n <= len(r.tokens) + d - r.num_cached
        r.draft_tokens = []
        r.num_cached += n - d
        if r.num_cached == len(r.tokens) and r.append_token(token()):
            sched.finish(r)
    sched.block_manager.check_invariants()


def _drive(sched, max_iters=200):
    """Minimal engine loop: schedule, run, retire, until nothing is
    left. Returns the per-iteration batches' (kind, num_scheduled)."""
    seen = []
    for _ in range(max_iters):
        if not sched.has_unfinished():
            break
        batch = sched.schedule()
        seen.append((batch.kind, list(batch.num_scheduled)))
        _run_batch(sched, batch)
    assert not sched.has_unfinished(), "starved requests remain"
    return seen


def test_scheduler_interleaves_prefill_and_decode():
    """Prompts that fit the budget prefill whole in one batch, then
    decode; a late arrival joins the decoding rows in a MIXED batch,
    behind them."""
    bm = BlockManager(num_blocks=64, block_size=4)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4,
                                      max_batched_tokens=64))
    for i in range(3):
        s.add(_req(i, n_prompt=5, max_new=3, arrival=float(i)))
    b = s.schedule()
    assert b.kind == "prefill" and b.num_scheduled == [5, 5, 5]
    _run_batch(s, b)
    b = s.schedule()
    assert b.kind == "decode" and b.num_scheduled == [1, 1, 1]
    _run_batch(s, b)
    s.add(_req("late", n_prompt=6, max_new=2, arrival=9.0))
    b = s.schedule()
    assert b.kind == "mixed"
    assert [r.request_id for r in b.requests] == ["0", "1", "2", "late"]
    assert b.num_scheduled == [1, 1, 1, 6]
    _run_batch(s, b)
    _drive(s)
    assert bm.num_free_blocks == 64


def test_scheduler_token_budget_splits_prefill_batches():
    """The raw budget is filled to the token: the prompt that crosses it
    is cut there, and goes on next iteration behind the decode row."""
    bm = BlockManager(num_blocks=64, block_size=4)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=8,
                                      max_batched_tokens=10))
    reqs = [_req(i, n_prompt=6, max_new=2, arrival=float(i))
            for i in range(4)]
    for r in reqs:
        s.add(r)
    b1 = s.schedule()
    assert b1.kind == "prefill"
    assert [r.request_id for r in b1.requests] == ["0", "1"]
    assert b1.num_scheduled == [6, 4]           # 6+6 > 10: cut at 10
    assert reqs[1].was_chunked and not reqs[0].was_chunked
    _run_batch(s, b1)
    b2 = s.schedule()
    # decode row, then the split prompt's rest, then new admissions
    assert [r.request_id for r in b2.requests] == ["0", "1", "2", "3"]
    assert b2.num_scheduled == [1, 2, 6, 1] and b2.kind == "mixed"
    assert s.num_prefill_chunks == 3    # 1's two pieces and 3's first
    _run_batch(s, b2)
    _drive(s)
    assert all(r.is_finished for r in reqs)


def test_scheduler_overbudget_prompt_admitted_alone():
    """A prompt over the whole budget is not refused and not starved: it
    arrives as budget-sized chunks, and samples only after the last."""
    bm = BlockManager(num_blocks=64, block_size=4)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=8,
                                      max_batched_tokens=8))
    big = _req("big", n_prompt=20, max_new=1)
    s.add(big)
    seen = _drive(s)
    assert seen == [("prefill", [8]), ("prefill", [8]), ("prefill", [4])]
    assert s.num_prefill_chunks == 3 and big.generated == [7]


def test_scheduler_preempts_latest_arrival_on_oom():
    """Two requests decoding in a cache with room for only one to grow:
    the LATER arrival is evicted, reclaims its blocks, lands at the
    front of the waiting queue, and its progress is preserved."""
    bm = BlockManager(num_blocks=4, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4))
    a = _req("a", n_prompt=4, max_new=8, arrival=1.0)
    b = _req("b", n_prompt=4, max_new=8, arrival=2.0)
    for r in (a, b):
        s.add(r)
    batch = s.schedule()       # both prefill: 2 blocks each, cache full
    assert [r.request_id for r in batch.requests] == ["a", "b"]
    _run_batch(s, batch)
    batch = s.schedule()       # both need a slot; only b's eviction frees one
    assert batch.kind == "decode" and batch.num_scheduled == [1]
    assert [r.request_id for r in batch.requests] == ["a"]
    assert [r.request_id for r in batch.preempted] == ["b"]
    assert b.status == RequestStatus.WAITING
    assert b.num_cached == 0 and len(b.tokens) == 5  # progress kept
    assert b.num_preemptions == 1
    assert s.waiting[0] is b
    bm.check_invariants()


def test_scheduler_starvation_guard_all_requests_finish():
    """More requests than max_num_seqs and a tight cache: every request
    (including preempted ones) must still run to completion — FCFS
    admission + evict-from-the-back guarantees forward progress."""
    bm = BlockManager(num_blocks=8, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=2,
                                      max_batched_tokens=16))
    reqs = [_req(i, n_prompt=3 + (i % 3), max_new=4, arrival=float(i))
            for i in range(6)]
    for r in reqs:
        s.add(r)
    _drive(s)
    assert all(r.is_finished for r in reqs)
    assert bm.num_free_blocks == 8


def test_scheduler_randomized_storm():
    """Random arrivals + tight memory + a budget that cuts prompts:
    preempted requests re-admit and finish; block accounting and the
    batch contract (``_run_batch``) hold at every iteration."""
    rng = np.random.default_rng(1)
    bm = BlockManager(num_blocks=7, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=3,
                                      max_batched_tokens=5))
    reqs = []
    for it in range(400):
        if len(reqs) < 20 and rng.random() < 0.2:
            r = _req(f"s{len(reqs)}", n_prompt=int(rng.integers(1, 8)),
                     max_new=int(rng.integers(1, 6)), arrival=float(it))
            reqs.append(r)
            s.add(r)
        if not s.has_unfinished():
            continue
        _run_batch(s, s.schedule(), lambda: int(rng.integers(0, 100)))
    _drive(s, max_iters=500)
    assert len(reqs) == 20 and all(r.is_finished for r in reqs)
    assert bm.num_free_blocks == 7
    assert s.num_preemptions > 0 and s.num_prefill_chunks > 0


def test_scheduler_abort():
    bm = BlockManager(num_blocks=8, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4))
    a, b = _req("a", 4), _req("b", 4)
    s.add(a), s.add(b)
    s.schedule()
    assert s.abort("a") and not s.abort("zz")
    assert a.status == RequestStatus.FINISHED
    assert "a" not in [r.request_id for r in s.running]
    bm.check_invariants()


# -- the mixed policy, pass by pass -----------------------------------------
def _mixed_decode_continuation_admission():
    """Pass A before B before C: the decode row, then the split prompt's
    next piece, then the newcomer with what is left of the budget."""
    bm = BlockManager(num_blocks=64, block_size=4)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4, max_batched_tokens=8))
    s.add(_req("dec", n_prompt=2, max_new=8, arrival=1.0))
    _run_batch(s, s.schedule())
    s.add(_req("mid", n_prompt=12, max_new=8, arrival=2.0))
    b = s.schedule()
    assert [r.request_id for r in b.requests] == ["dec", "mid"]
    assert b.num_scheduled == [1, 7]
    _run_batch(s, b)
    # the newcomer outranks both and still queues behind their rows
    new = Request(request_id="new", prompt_ids=[1, 2, 3],
                  sampling=SamplingParams(max_new_tokens=8, priority=-1))
    s.add(new)
    b = s.schedule()
    assert b.kind == "mixed"
    assert [r.request_id for r in b.requests] == ["dec", "mid", "new"]
    assert b.num_scheduled == [1, 5, 2]
    assert new.was_chunked and new.status == RequestStatus.RUNNING
    _run_batch(s, b)
    b = s.schedule()                 # new's last token, no sample before
    assert [r.request_id for r in b.requests] == ["dec", "mid", "new"]
    assert b.num_scheduled == [1, 1, 1] and new.num_generated == 0
    _run_batch(s, b)
    assert new.num_generated == 1


def _mixed_continuation_keeps_its_seat():
    """A prompt in the middle of its chunks is RUNNING: a more important
    arrival does not take the seat, and the chunks do not restart."""
    bm = BlockManager(num_blocks=64, block_size=4)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=1, max_batched_tokens=4))
    big = _req("big", n_prompt=10, max_new=2, arrival=1.0)
    s.add(big)
    _run_batch(s, s.schedule())
    assert big.num_cached == 4 and big.was_chunked
    vip = Request(request_id="vip", prompt_ids=[1, 2],
                  sampling=SamplingParams(max_new_tokens=1, priority=-1))
    s.add(vip)
    seen = []
    while not big.is_finished:
        b = s.schedule()
        assert [r.request_id for r in b.requests] == ["big"]
        assert vip.status == RequestStatus.WAITING
        seen.append(b.num_scheduled[0])
        _run_batch(s, b)
    assert seen == [4, 2, 1]         # the rest of the prompt, one decode
    assert s.num_prefill_chunks == 3 and s.num_preemptions == 0
    b = s.schedule()
    assert [r.request_id for r in b.requests] == ["vip"]


def _mixed_verify_row_all_or_nothing():
    """A verify row costs 1 + its drafts, whole or not at all: the row
    the budget cannot verify sheds its drafts and decodes plainly."""
    bm = BlockManager(num_blocks=64, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=2, max_batched_tokens=4))
    a = _req("a", n_prompt=3, max_new=8, arrival=1.0)
    b = _req("b", n_prompt=1, max_new=8, arrival=2.0)
    s.add(a), s.add(b)
    _run_batch(s, s.schedule())
    a.draft_tokens, b.draft_tokens = [11, 12], [21, 22]
    batch = s.schedule()
    assert [r.request_id for r in batch.requests] == ["a", "b"]
    assert batch.num_scheduled == [3, 1] and batch.kind == "decode"
    assert a.draft_tokens == [11, 12] and b.draft_tokens == []
    # a's table covers its drafts' positions, b's only its own token
    assert len(bm.block_table("a")) == bm.blocks_needed(len(a.tokens) + 2)
    assert len(bm.block_table("b")) == bm.blocks_needed(len(b.tokens))
    _run_batch(s, batch)


def _mixed_table_holding_continuation():
    """A request that arrives with its table filled (a KV ship) waits
    for a seat like any other, then resumes mid-context on the blocks it
    holds: no fresh allocation, nothing recomputed."""
    bm = BlockManager(num_blocks=16, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=1, max_batched_tokens=8))
    run = _req("run", n_prompt=3, max_new=2, arrival=1.0)
    s.add(run)
    _run_batch(s, s.schedule())
    cont = _req("cont", n_prompt=7, max_new=2, arrival=2.0)
    shipped = list(bm.import_blocks("cont", 4))
    cont.num_cached = 4
    s.add_continuation(cont)
    b = s.schedule()
    assert [r.request_id for r in b.requests] == ["run"]   # no seat yet
    assert cont.status == RequestStatus.WAITING and bm.has_table("cont")
    _run_batch(s, b)
    assert run.is_finished
    b = s.schedule()
    assert [r.request_id for r in b.requests] == ["cont"]
    assert b.num_scheduled == [3] and b.kind == "prefill"
    assert s.num_continuation_resumes == 1
    assert bm.block_table("cont")[:2] == shipped
    _run_batch(s, b)
    assert cont.num_generated == 1 and cont.num_cached == 7


def _mixed_budget_equal_to_seats():
    """The smallest budget the config admits: every seat affords its
    decode token and nothing is left to admit with."""
    with pytest.raises(ValueError, match="max_batched_tokens"):
        SchedulerConfig(max_num_seqs=4, max_batched_tokens=3)
    bm = BlockManager(num_blocks=64, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=3, max_batched_tokens=3))
    reqs = [_req(i, n_prompt=1, max_new=3, arrival=float(i))
            for i in range(3)]
    late = _req("late", n_prompt=2, max_new=1, arrival=9.0)
    for r in reqs:
        s.add(r)
    b = s.schedule()
    assert b.num_scheduled == [1, 1, 1] and b.kind == "prefill"
    _run_batch(s, b)
    s.add(late)
    while not reqs[0].is_finished:
        b = s.schedule()
        assert b.num_scheduled == [1, 1, 1] and b.kind == "decode"
        assert late.status == RequestStatus.WAITING
        _run_batch(s, b)
    _drive(s)
    assert late.is_finished


def _mixed_idle():
    """Nothing to run is an idle batch, empty in every list; so is a
    lone arrival the pool cannot hold yet, which stays queued."""
    bm = BlockManager(num_blocks=2, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=2, max_batched_tokens=8))
    b = s.schedule()
    assert b.kind == "idle" and b.is_empty and b.num_scheduled == []
    assert not (b.preempted or b.swapped_in or b.expired)
    bm.allocate("squatter", 4)
    s.add(_req("w", n_prompt=3))
    b = s.schedule()
    assert b.kind == "idle" and b.is_empty and s.num_waiting == 1
    bm.free("squatter")
    b = s.schedule()
    assert b.kind == "prefill" and b.num_scheduled == [3]


@pytest.mark.parametrize("case", [
    _mixed_decode_continuation_admission,
    _mixed_continuation_keeps_its_seat,
    _mixed_verify_row_all_or_nothing,
    _mixed_table_holding_continuation,
    _mixed_budget_equal_to_seats,
    _mixed_idle,
], ids=lambda f: f.__name__.removeprefix("_mixed_"))
def test_schedule_mixed(case):
    case()


def test_request_and_sampling_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(request_id="x", prompt_ids=[])
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    r = _req("x", 3, max_new=2)
    assert r.append_token(5) is False
    assert r.append_token(6) is True      # max_new_tokens reached
    assert r.is_finished and r.generated == [5, 6]
    r2 = Request(request_id="y", prompt_ids=[1, 2],
                 sampling=SamplingParams(max_new_tokens=9,
                                         eos_token_id=42))
    assert r2.append_token(41) is False
    assert r2.append_token(42) is True    # EOS

# ---------------------------------------------------------------------------
# host swap pool + abort-leak invariants (ISSUE 6)
# ---------------------------------------------------------------------------
class _StubSwapper:
    """Model-free KV mover: records traffic, moves no bytes."""

    def __init__(self):
        self.out_calls = []
        self.in_calls = []

    def copy_out(self, request, dev_table, host_table):
        self.out_calls.append((request.request_id, list(dev_table),
                               list(host_table)))

    def copy_in(self, request, host_table, dev_table):
        self.in_calls.append((request.request_id, list(host_table),
                              list(dev_table)))


def test_block_manager_swap_accounting():
    bm = BlockManager(num_blocks=4, block_size=2, num_host_blocks=3)
    bm.allocate("a", 5)                      # 3 device blocks
    assert bm.can_swap_out("a", 5)
    dev, host = bm.swap_out("a", 5)
    assert len(dev) == 3 and len(host) == 3
    assert bm.num_free_blocks == 4           # device side fully back
    assert bm.num_free_host_blocks == 0
    assert not bm.has_table("a") and bm.has_host_table("a")
    bm.check_invariants()
    # restore: host slots come back, device blocks claimed again
    host2, dev2 = bm.swap_in("a")
    assert host2 == host and len(dev2) == 3
    assert bm.num_free_host_blocks == 3
    assert bm.num_free_blocks == 1
    bm.check_invariants()
    assert bm.free("a") == 3
    bm.check_invariants()


def test_block_manager_swap_rejects_when_pool_small():
    bm = BlockManager(num_blocks=8, block_size=2, num_host_blocks=1)
    bm.allocate("a", 6)                      # needs 3 host slots
    assert not bm.can_swap_out("a", 6)
    with pytest.raises(NoFreeBlocksError, match="swap out"):
        bm.swap_out("a", 6)
    # no-pool manager never swaps
    bm0 = BlockManager(num_blocks=4, block_size=2)
    bm0.allocate("a", 2)
    assert not bm0.can_swap_out("a", 2)


def test_block_manager_free_releases_host_slots_too():
    """The abort-while-swapped leak class: free() must drop BOTH
    sides, and is idempotent."""
    bm = BlockManager(num_blocks=4, block_size=2, num_host_blocks=4)
    bm.allocate("a", 4)
    bm.swap_out("a", 4)
    assert bm.num_free_host_blocks == 2
    assert bm.free("a") == 0                 # no device blocks held
    assert bm.num_free_host_blocks == 4      # host slots reclaimed
    assert bm.free("a") == 0
    bm.check_invariants()


def test_scheduler_swap_preempts_and_restores():
    """Eviction with a host pool spills instead of recomputing: the
    victim keeps num_cached, rejoins running via swap-in when blocks
    free, and the swapper sees matching out/in traffic."""
    bm = BlockManager(num_blocks=4, block_size=2, num_host_blocks=4)
    sw = _StubSwapper()
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4), swap_mode="host",
                  kv_swapper=sw)
    a = _req("a", n_prompt=4, max_new=8, arrival=1.0)
    b = _req("b", n_prompt=4, max_new=8, arrival=2.0)
    for r in (a, b):
        s.add(r)
    _run_batch(s, s.schedule())              # both prefill, cache full
    batch = s.schedule()                     # OOM -> b swaps out
    assert [r.request_id for r in batch.requests] == ["a"]
    assert [r.request_id for r in batch.preempted] == ["b"]
    assert b.status == RequestStatus.SWAPPED
    assert b.num_cached == 4                 # cached prefix KEPT
    assert b.num_swaps == 1 and s.num_swap_outs == 1
    assert len(sw.out_calls) == 1
    bm.check_invariants()
    # finish a -> blocks free -> b swaps back in and decodes
    a.num_cached += 1
    while not a.append_token(7):
        pass
    s.finish(a)
    batch = s.schedule()
    assert [r.request_id for r in batch.swapped_in] == ["b"]
    assert batch.kind == "decode" and batch.num_scheduled == [1]
    assert [r.request_id for r in batch.requests] == ["b"]
    assert b.status == RequestStatus.RUNNING
    assert s.num_swap_ins == 1 and len(sw.in_calls) == 1
    # the restored device table covers the cached prefix
    assert len(bm.block_table("b")) >= 2
    bm.check_invariants()


def test_scheduler_host_pool_exhaustion_falls_back_to_recompute():
    """A full host pool must not deadlock eviction: the victim falls
    back to the recompute path (WAITING, num_cached reset)."""
    bm = BlockManager(num_blocks=4, block_size=2, num_host_blocks=1)
    sw = _StubSwapper()
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4), swap_mode="host",
                  kv_swapper=sw)
    a = _req("a", n_prompt=4, max_new=8, arrival=1.0)
    b = _req("b", n_prompt=4, max_new=8, arrival=2.0)
    for r in (a, b):
        s.add(r)
    _run_batch(s, s.schedule())
    batch = s.schedule()                     # b evicted; pool too small
    assert [r.request_id for r in batch.preempted] == ["b"]
    assert b.status == RequestStatus.WAITING
    assert b.num_cached == 0 and s.num_swap_outs == 0
    assert sw.out_calls == []
    bm.check_invariants()


def test_scheduler_torn_spill_copy_frees_host_slots():
    """A copy_out that dies mid-spill must not strand the victim's host
    slots (the leaked-resource-on-raise class this PR's linter flags):
    the slots come back and the victim demotes to the recompute path."""
    class _TornSwapper(_StubSwapper):
        def copy_out(self, request, dev_table, host_table):
            raise RuntimeError("DMA torn mid-frame")

    bm = BlockManager(num_blocks=4, block_size=2, num_host_blocks=4)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=4), swap_mode="host",
                  kv_swapper=_TornSwapper())
    a = _req("a", n_prompt=4, max_new=8, arrival=1.0)
    b = _req("b", n_prompt=4, max_new=8, arrival=2.0)
    for r in (a, b):
        s.add(r)
    _run_batch(s, s.schedule())
    batch = s.schedule()                     # OOM -> spill of b tears
    assert [r.request_id for r in batch.preempted] == ["b"]
    assert b.status == RequestStatus.WAITING  # recompute, not SWAPPED
    assert b.num_cached == 0
    assert s.num_swap_outs == 0              # the spill never counted
    assert not bm.has_host_table("b")        # host slots reclaimed
    assert bm.num_free_host_blocks == 4
    bm.check_invariants()


def test_scheduler_priority_orders_admission_and_eviction():
    """priority < 0 beats FCFS: a late VIP admits first and is never
    the eviction victim while a lower-priority peer remains."""
    bm = BlockManager(num_blocks=4, block_size=2)
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=2))
    lo = Request(request_id="lo", prompt_ids=[1, 2, 3, 4],
                 sampling=SamplingParams(max_new_tokens=8))
    lo.arrival_time = 1.0
    vip = Request(request_id="vip", prompt_ids=[1, 2, 3, 4],
                  sampling=SamplingParams(max_new_tokens=8, priority=-1))
    vip.arrival_time = 2.0                   # later, but outranks
    s.add(lo), s.add(vip)
    batch = s.schedule()
    assert [r.request_id for r in batch.requests] == ["vip", "lo"]
    _run_batch(s, batch)
    batch = s.schedule()                     # OOM: LO is the victim
    assert [r.request_id for r in batch.requests] == ["vip"]
    assert [r.request_id for r in batch.preempted] == ["lo"]
    assert lo.status == RequestStatus.WAITING
    bm.check_invariants()


def test_scheduler_expire_deadlines_every_queue():
    import time as _time

    bm = BlockManager(num_blocks=8, block_size=2, num_host_blocks=8)
    sw = _StubSwapper()
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=2), swap_mode="host",
                  kv_swapper=sw)
    mk = lambda rid: Request(  # noqa: E731
        request_id=rid, prompt_ids=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=4, deadline_ms=1e-3))
    r_wait, r_run, r_swap = mk("w"), mk("r"), mk("s")
    # place one per queue, bypassing schedule for direct control
    s.waiting.append(r_wait)
    bm.allocate("r", 3)
    r_run.status = RequestStatus.RUNNING
    s.running.append(r_run)
    bm.allocate("s", 3)
    r_swap.num_cached = 3
    bm.swap_out("s", 3)
    r_swap.status = RequestStatus.SWAPPED
    s.swapped.append(r_swap)
    _time.sleep(0.002)
    expired = s.expire_deadlines()
    assert sorted(r.request_id for r in expired) == ["r", "s", "w"]
    assert all(r.finish_reason == "expired" for r in expired)
    assert not s.has_unfinished()
    assert bm.num_free_blocks == 8 and bm.num_free_host_blocks == 8
    bm.check_invariants()


def test_randomized_abort_interleaving_never_leaks_blocks():
    """Satellite-1 acceptance: after ANY interleaving of admission,
    decode, preemption (swap AND recompute), expiry, and abort —
    across every lifecycle state — both free lists return to full.
    400 iterations of a seeded random storm, invariants checked every
    step."""
    rng = np.random.default_rng(7)
    bm = BlockManager(num_blocks=10, block_size=2, num_host_blocks=4)
    sw = _StubSwapper()
    s = Scheduler(bm, SchedulerConfig(max_num_seqs=3,
                                      max_batched_tokens=6),
                  swap_mode="host", kv_swapper=sw)
    reqs = []
    n_aborted = 0
    for it in range(400):
        if len(reqs) < 24 and rng.random() < 0.25:
            r = Request(
                request_id=f"r{len(reqs)}",
                prompt_ids=list(range(1, int(rng.integers(2, 9)))),
                sampling=SamplingParams(
                    max_new_tokens=int(rng.integers(1, 6)),
                    priority=int(rng.integers(-1, 2)),
                    # a slice of requests carries a TTL that will
                    # expire mid-storm
                    deadline_ms=(float(rng.integers(1, 20))
                                 if rng.random() < 0.3 else None)))
            r.arrival_time = float(it)
            reqs.append(r)
            s.add(r)
        # random abort of a random LIVE request, in ANY state
        # (waiting / running / swapped alike)
        if rng.random() < 0.15:
            live = [r for r in reqs if not r.is_finished]
            if live:
                victim = live[int(rng.integers(0, len(live)))]
                assert s.abort(victim.request_id)
                n_aborted += 1
        if not s.has_unfinished():
            continue
        _run_batch(s, s.schedule(), lambda: int(rng.integers(0, 100)))
    # drain the stragglers (aborting a random half on the way out)
    guard = 0
    while s.has_unfinished():
        guard += 1
        assert guard < 300, "storm failed to converge"
        live = [r for r in reqs if not r.is_finished]
        if live and rng.random() < 0.3:
            s.abort(live[0].request_id)
            n_aborted += 1
        _run_batch(s, s.schedule(), lambda: int(rng.integers(0, 100)))
    assert len(reqs) == 24 and all(r.is_finished for r in reqs)
    assert n_aborted > 0, "storm never exercised abort"
    # the satellite's pin: NOTHING leaks, device or host side
    assert bm.num_free_blocks == bm.num_blocks
    assert bm.num_free_host_blocks == bm.num_host_blocks
    bm.check_invariants()


def test_prefix_cache_cow_refcount_randomized_storm():
    """ISSUE-9 satellite: randomized storm on the PREFIX-CACHING
    allocator. Admissions draw from a prompt pool with genuine shared
    prefixes (so blocks really get refcounted across requests),
    growth follows the scheduler's chunked-prefill shape (write_from
    mid-prompt) then decodes, aborts strike at any phase, and host
    swap in/out interleaves throughout. COW pairs are drained exactly
    the way the engine drains them (take_cow_pairs before each step)
    and the exact-accounting invariants must hold after EVERY
    operation; at the end both free lists return to full."""
    rng = np.random.default_rng(5)
    bm = BlockManager(num_blocks=24, block_size=4, num_host_blocks=8,
                      enable_prefix_cache=True)
    # three 16-token stems, each with divergent tails; the bare
    # 8-token stem (2 exactly-full blocks) is the full-prompt-hit
    # case whose capped write forces COW while a peer holds the block
    stems = [list(map(int, rng.integers(0, 40, size=16)))
             for _ in range(3)]
    pool = [stem[:k] + list(map(int, rng.integers(40, 80, size=t)))
            for stem in stems
            for (k, t) in ((16, 3), (16, 6), (12, 5), (8, 0))]
    live = {}     # rid -> {"tokens", "covered", "target"}
    swapped = {}  # rid -> same dict, parked on host slots

    def drain_cow():
        for src, dst in bm.take_cow_pairs():
            assert src != dst, "COW copied a block onto itself"
            assert bm.ref_count(dst) >= 1, \
                "COW destination freed before the copy was drained"

    def pick(d):
        return list(d)[int(rng.integers(0, len(d)))]

    for it in range(1500):
        op = int(rng.integers(0, 5))
        if op == 0:  # admit, scheduler-shaped (match -> eff cap -> chunk)
            rid = f"s{it}"
            tokens = list(pool[int(rng.integers(0, len(pool)))])
            total = len(tokens)
            hit = bm.match_prefix(tokens)
            eff = min(hit, total - 1)
            n = int(rng.integers(1, total - eff + 1))
            try:
                bm.allocate(rid, eff + n, tokens=tokens)
            except NoFreeBlocksError:
                bm.check_invariants()
                continue
            covered = bm.last_hit_tokens + n
            live[rid] = {"tokens": tokens, "covered": covered,
                         "target": total + int(rng.integers(1, 6))}
            bm.commit_prefix(rid, tokens, covered)
        elif op == 1 and live:  # grow: chunk continuation, then decode
            rid = pick(live)
            st = live[rid]
            if st["covered"] >= st["target"]:
                bm.free(rid)
                live.pop(rid)
            else:
                remaining_prompt = len(st["tokens"]) - st["covered"]
                n = (int(rng.integers(1, remaining_prompt + 1))
                     if remaining_prompt > 0 else 1)
                try:
                    bm.append_slot(rid, st["covered"] + n,
                                   write_from=st["covered"])
                except NoFreeBlocksError:
                    bm.check_invariants()
                    continue
                st["covered"] += n
                bm.commit_prefix(rid, st["tokens"], st["covered"])
        elif op == 2 and live:  # abort/finish at any phase
            rid = pick(live)
            bm.free(rid)
            live.pop(rid)
        elif op == 3 and live:  # swap out (drops device refs)
            rid = pick(live)
            if bm.can_swap_out(rid, live[rid]["covered"]):
                bm.swap_out(rid, live[rid]["covered"])
                swapped[rid] = live.pop(rid)
        elif op == 4 and swapped:  # swap back in, or abort-while-swapped
            rid = pick(swapped)
            if rng.random() < 0.25:
                bm.free(rid)
                swapped.pop(rid)
            elif bm.can_swap_in(rid):
                bm.swap_in(rid)
                live[rid] = swapped.pop(rid)
        drain_cow()
        bm.check_invariants()
    for rid in list(live) + list(swapped):
        bm.free(rid)
    drain_cow()
    bm.check_invariants()
    assert bm.num_free_blocks == bm.num_blocks
    assert bm.num_free_host_blocks == bm.num_host_blocks
    # the storm actually exercised the machinery it pins
    assert bm.num_prefix_hits > 0, "no admission ever shared a prefix"
    assert bm.num_cow_copies > 0, "no write ever copy-on-wrote"


# ---------------------------------------------------------------------------
# fleet KV-ship: export_blocks / import_blocks (ISSUE 13)
# ---------------------------------------------------------------------------
def test_block_manager_export_import_basics():
    bm = BlockManager(num_blocks=8, block_size=4)
    bm.allocate("src", 10)                      # 3 blocks
    # export is read-only: leading blocks, no accounting change
    free_before = bm.num_free_blocks
    exported = bm.export_blocks("src", 7)       # 2 blocks cover 7
    assert exported == bm.block_table("src")[:2]
    assert bm.num_free_blocks == free_before
    bm.check_invariants()
    with pytest.raises(KeyError):
        bm.export_blocks("nope", 4)
    with pytest.raises(ValueError):
        bm.export_blocks("src", 999)            # table too short
    # import claims fresh refcount-1 unregistered blocks
    table = bm.import_blocks("dst", 7)
    assert len(table) == 2 and bm.has_table("dst")
    assert all(bm.ref_count(b) == 1 for b in table)
    assert set(table).isdisjoint(exported)
    bm.check_invariants()
    with pytest.raises(ValueError):
        bm.import_blocks("dst", 4)              # table already exists
    with pytest.raises(NoFreeBlocksError):
        bm.import_blocks("big", 100)
    bm.free("src")
    bm.free("dst")
    bm.check_invariants()
    assert bm.num_free_blocks == bm.num_blocks


def test_export_import_interleaved_with_cow_swap_storm():
    """ISSUE-13 satellite: the COW/refcount/swap storm with randomized
    export/import interleaved. Exports must be pure reads; imported
    tables join the same lifecycle (growth, COW via prefix commits,
    swap, abort) and the exact-accounting invariants hold after every
    operation; at the end both free lists return to full and the trie
    bijection (checked inside ``check_invariants``) survives."""
    rng = np.random.default_rng(13)
    bm = BlockManager(num_blocks=24, block_size=4, num_host_blocks=8,
                      enable_prefix_cache=True)
    stems = [list(map(int, rng.integers(0, 40, size=16)))
             for _ in range(3)]
    pool = [stem[:k] + list(map(int, rng.integers(40, 80, size=t)))
            for stem in stems
            for (k, t) in ((16, 3), (16, 6), (12, 5), (8, 0))]
    live = {}
    swapped = {}
    n_exports = n_imports = 0

    def drain_cow():
        for src, dst in bm.take_cow_pairs():
            assert src != dst
            assert bm.ref_count(dst) >= 1

    def pick(d):
        return list(d)[int(rng.integers(0, len(d)))]

    for it in range(1500):
        op = int(rng.integers(0, 7))
        if op == 0:  # admit, scheduler-shaped
            rid = f"s{it}"
            tokens = list(pool[int(rng.integers(0, len(pool)))])
            total = len(tokens)
            hit = bm.match_prefix(tokens)
            eff = min(hit, total - 1)
            n = int(rng.integers(1, total - eff + 1))
            try:
                bm.allocate(rid, eff + n, tokens=tokens)
            except NoFreeBlocksError:
                bm.check_invariants()
                continue
            covered = bm.last_hit_tokens + n
            live[rid] = {"tokens": tokens, "covered": covered,
                         "target": total + int(rng.integers(1, 6))}
            bm.commit_prefix(rid, tokens, covered)
        elif op == 1 and live:  # grow
            rid = pick(live)
            st = live[rid]
            if st["covered"] >= st["target"]:
                bm.free(rid)
                live.pop(rid)
            else:
                remaining = len(st["tokens"]) - st["covered"]
                n = (int(rng.integers(1, remaining + 1))
                     if remaining > 0 else 1)
                try:
                    bm.append_slot(rid, st["covered"] + n,
                                   write_from=st["covered"])
                except NoFreeBlocksError:
                    bm.check_invariants()
                    continue
                st["covered"] += n
                bm.commit_prefix(rid, st["tokens"], st["covered"])
        elif op == 2 and live:  # abort/finish
            rid = pick(live)
            bm.free(rid)
            live.pop(rid)
        elif op == 3 and live:  # swap out
            rid = pick(live)
            if bm.can_swap_out(rid, live[rid]["covered"]):
                bm.swap_out(rid, live[rid]["covered"])
                swapped[rid] = live.pop(rid)
        elif op == 4 and swapped:  # swap in / abort-while-swapped
            rid = pick(swapped)
            if rng.random() < 0.25:
                bm.free(rid)
                swapped.pop(rid)
            elif bm.can_swap_in(rid):
                bm.swap_in(rid)
                live[rid] = swapped.pop(rid)
        elif op == 5 and live:  # export: a pure read
            rid = pick(live)
            covered = live[rid]["covered"]
            if covered > 0:
                free_before = bm.num_free_blocks
                table = bm.export_blocks(rid, covered)
                assert table == bm.block_table(rid)[:len(table)]
                assert bm.num_free_blocks == free_before
                n_exports += 1
        elif op == 6:  # import: fresh blocks enter the lifecycle
            rid = f"i{it}"
            tokens = list(pool[int(rng.integers(0, len(pool)))])
            covered = int(rng.integers(1, len(tokens)))
            try:
                table = bm.import_blocks(rid, covered)
            except NoFreeBlocksError:
                bm.check_invariants()
                continue
            assert all(bm.ref_count(b) == 1 for b in table)
            live[rid] = {"tokens": tokens, "covered": covered,
                         "target": len(tokens)
                         + int(rng.integers(1, 6))}
            # the engine registers imported full blocks in the trie
            # (peers can prefix-hit onto shipped KV)
            bm.commit_prefix(rid, tokens, covered)
            n_imports += 1
        drain_cow()
        bm.check_invariants()
    for rid in list(live) + list(swapped):
        bm.free(rid)
    drain_cow()
    bm.check_invariants()
    assert bm.num_free_blocks == bm.num_blocks
    assert bm.num_free_host_blocks == bm.num_host_blocks
    assert n_exports > 0, "storm never exported"
    assert n_imports > 0, "storm never imported"
    assert bm.num_cow_copies > 0, "no write ever copy-on-wrote"
