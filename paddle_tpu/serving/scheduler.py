"""Iteration-level (continuous-batching) scheduler.

Orca's insight, as shipped by vLLM: scheduling decisions happen every
model iteration, not per request. Each call to :meth:`schedule` emits
ONE mixed batch under a raw token budget — a token for every running
request that has caught up, then chunks of the prompts still being
prefilled, then new admissions, their prompts chunked too — so
late-arriving requests join the running batch at the next iteration
boundary instead of waiting for a full drain, and a long prompt never
stalls the decode rows beside it.

Preemption: when a row needs a block and none are free, the
lowest-priority running request (largest ``(priority, arrival)`` key)
is evicted — never a higher-priority one — until the victim set frees
enough. Priority-then-FCFS admission plus eviction-from-the-back gives
the most important request a monotonically growing claim on the cache,
so every admitted request eventually finishes (the starvation guard
pinned by tests/test_serving.py).

Eviction has two modes (``swap_mode``): ``recompute`` resets the victim
to WAITING and recomputes its whole prefix on re-admission (vLLM's
default); ``host`` spills the victim's KV blocks to the
:class:`BlockManager` host pool through the engine's KV swapper and
restores them on re-admission — no recompute, token-identical by
construction (parity pinned by tests/test_serving_resilience.py).

Deadlines: every :meth:`schedule` call first expires requests whose
``deadline_ms`` TTL has passed — wherever they are (waiting, running,
swapped) — freeing their blocks and reporting them in
``ScheduledBatch.expired`` so the engine can emit structured
``finish_reason='expired'`` outputs."""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional

from paddle_tpu.serving.block_manager import BlockManager, NoFreeBlocksError
from paddle_tpu.serving.request import Request, RequestStatus

__all__ = ["SchedulerConfig", "ScheduledBatch", "Scheduler"]


@dataclass
class SchedulerConfig:
    """Admission/batching knobs.

    ``max_num_seqs``   — max concurrently RUNNING requests (the rows of
                         a batch).
    ``max_batched_tokens`` — per-iteration RAW token budget: the tokens
                         of all rows together. The ragged step pads
                         nothing, so the raw count is the compiled work;
                         a prompt over what is left of it runs as chunks.
    """

    max_num_seqs: int = 8
    max_batched_tokens: int = 2048

    def __post_init__(self):
        if self.max_num_seqs < 1:
            raise ValueError("max_num_seqs must be >= 1")
        if self.max_batched_tokens < self.max_num_seqs:
            raise ValueError(
                "max_batched_tokens must be >= max_num_seqs (every "
                "running row must afford its decode token)")


@dataclass
class ScheduledBatch:
    """One iteration's work: requests + phase. ``preempted`` lists
    requests evicted while forming this batch (reset to WAITING for
    recompute, or SWAPPED to the host pool); ``swapped_in`` lists
    requests restored from the host pool into ``running`` this
    iteration; ``expired`` lists requests whose deadline passed (already
    terminal, blocks freed — the engine emits their outputs)."""

    kind: str                       # "prefill" | "decode" | "mixed" | "idle"
    requests: List[Request] = field(default_factory=list)
    preempted: List[Request] = field(default_factory=list)
    swapped_in: List[Request] = field(default_factory=list)
    expired: List[Request] = field(default_factory=list)
    # tokens scheduled per row (parallel to ``requests``)
    num_scheduled: List[int] = field(default_factory=list)

    @property
    def is_empty(self) -> bool:
        return not self.requests


class Scheduler:
    def __init__(self, block_manager: BlockManager,
                 config: Optional[SchedulerConfig] = None,
                 swap_mode: str = "recompute", kv_swapper=None):
        """``swap_mode='host'`` needs a ``kv_swapper`` — the engine-side
        mover with ``copy_out(request, dev_table, host_table)`` /
        ``copy_in(request, host_table, dev_table)`` — plus a
        BlockManager built with ``num_host_blocks > 0``. When the host
        pool is full (or absent) eviction falls back to recompute, so
        ``host`` mode degrades gracefully rather than deadlocking."""
        if swap_mode not in ("recompute", "host"):
            raise ValueError(f"unknown swap_mode {swap_mode!r} "
                             f"(want 'recompute' or 'host')")
        if swap_mode == "host" and kv_swapper is None:
            raise ValueError("swap_mode='host' needs a kv_swapper")
        self.block_manager = block_manager
        self.config = config or SchedulerConfig()
        self.swap_mode = swap_mode
        self.kv_swapper = kv_swapper
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []
        self.swapped: List[Request] = []
        self.num_preemptions = 0
        self.num_swap_outs = 0
        self.num_swap_ins = 0
        # pieces scheduled for prompts the budget ever split (chunked
        # prefill; every piece of a split prompt counts, including the
        # final one)
        self.num_prefill_chunks = 0
        # KV-ship continuations admitted with a pre-filled table (the
        # imported blocks may hold bytes that landed through a
        # cross-TP-degree reshard — scheduling is layout-agnostic, so
        # this counter is the only place the scheduler sees them)
        self.num_continuation_resumes = 0
        # prompt tokens of the requests admitted from the waiting queue
        # (the denominator of the prefix cache's hit share)
        self.num_admitted_prompt_tokens = 0
        # tiered-KV relief hook (engine-installed): called with the
        # OOM'ing request before any preemption; True means >= 1 device
        # block was freed by demoting cold content to the host tier, so
        # the claim retries instead of evicting a batch peer. Each True
        # strictly grows the free list, so every retry loop below stays
        # bounded.
        self.tier_relief = None

    # -- queue ops -------------------------------------------------------
    def add(self, request: Request):
        request.status = RequestStatus.WAITING
        self.waiting.append(request)

    def add_continuation(self, request: Request):
        """Admit a request that ALREADY holds a device table covering
        ``request.num_cached`` tokens (fleet KV-ship import: the engine
        claimed the blocks and scattered peer-computed bytes into
        them). It queues WAITING like any arrival — seats are enforced
        at admission, and ``abort``/``expire_deadlines`` free blocks on
        every queue so the held table can't leak — but the mixed
        scheduler's admission pass recognizes the existing table and
        skips the fresh ``allocate``, continuing the row mid-context
        like a chunked-prefill resume. If it is later evicted,
        ``_evict`` resets ``num_cached`` and frees the imported blocks,
        so recompute-from-scratch remains the universal fallback."""
        request.status = RequestStatus.WAITING
        self.waiting.append(request)

    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running or self.swapped)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_waiting_tokens(self) -> int:
        """Uncached tokens queued for prefill — the work ahead of a new
        arrival, which the admission controller's TTFT estimate weighs
        so long prompts can't sneak past the SLO gate."""
        return sum(len(r.tokens_to_run()) for r in self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    @property
    def num_swapped(self) -> int:
        return len(self.swapped)

    def finish(self, request: Request):
        """Completion: reclaim blocks, drop from the running set."""
        self.block_manager.free(request.request_id)
        if request in self.running:
            self.running.remove(request)

    def abort(self, request_id: str, reason: str = "aborted:user") -> bool:
        """Cancel a request wherever it is — waiting, running, or
        swapped (device blocks AND host slots freed); True when found."""
        for q in (self.running, self.waiting, self.swapped):
            for r in list(q):
                if r.request_id == request_id:
                    self.block_manager.free(r.request_id)
                    q.remove(r)
                    r.abort(reason)
                    return True
        return False

    def expire_deadlines(self, now: Optional[float] = None
                         ) -> List[Request]:
        """TTL sweep: terminate every request whose deadline passed,
        on every lifecycle queue, freeing its blocks/slots. Returns the
        expired requests (engine emits their structured outputs)."""
        now = time.monotonic() if now is None else now
        out: List[Request] = []
        for q in (self.running, self.waiting, self.swapped):
            for r in list(q):
                if r.expired(now):
                    self.block_manager.free(r.request_id)
                    q.remove(r)
                    r.abort("expired")
                    out.append(r)
        return out

    # -- preemption ------------------------------------------------------
    def _evict(self, victim: Request):
        """Evict ``victim`` from the running set: spill its KV to the
        host pool when swap is enabled and slots are available (the
        cached prefix survives, restore is a pure copy), else reset to
        WAITING for recompute. Either way every device block returns to
        the free list before this returns."""
        self.running.remove(victim)
        self.num_preemptions += 1
        if (self.swap_mode == "host" and victim.num_cached > 0
                and self.block_manager.can_swap_out(victim.request_id,
                                                    victim.num_cached)):
            dev, host = self.block_manager.swap_out(victim.request_id,
                                                    victim.num_cached)
            # copy NOW: the freed device blocks' bytes are intact until
            # the next compiled step writes them, and nothing dispatches
            # before schedule() returns
            try:
                self.kv_swapper.copy_out(victim, dev, host)
                victim.swap_out()
            except Exception:
                # a torn spill copy must not strand the host slots:
                # drop them and demote to the recompute path (nothing
                # was emitted, so the prompt replays exactly)
                self.block_manager.free_host(victim.request_id)
                victim.preempt()
                self.waiting.appendleft(victim)
                return
            self.swapped.append(victim)
            self.num_swap_outs += 1
        else:
            self.block_manager.free(victim.request_id)
            victim.preempt()
            self.waiting.appendleft(victim)

    def _preempt_one(self, for_request: Request) -> Optional[Request]:
        """Evict the lowest-priority running request — largest
        ``(priority, arrival)`` key — to free blocks for
        ``for_request``, but never a HIGHER-priority one: when
        ``for_request`` is itself the lowest priority, returns None and
        the caller self-preempts. A recompute victim goes to the FRONT
        of the waiting queue so it is not starved behind newer
        arrivals; a swapped victim waits in the swap queue."""
        candidates = [r for r in self.running
                      if r is not for_request
                      and r.sort_key >= for_request.sort_key]
        if not candidates:
            return None
        victim = max(candidates, key=lambda r: r.sort_key)
        self._evict(victim)
        return victim

    def _swap_in_ready(self) -> List[Request]:
        """Restore swapped requests (most important first) while device
        blocks allow; they rejoin ``running`` and take a row of this
        very iteration's batch."""
        restored: List[Request] = []
        for r in sorted(self.swapped, key=lambda r: r.sort_key):
            if len(self.running) + len(restored) >= self.config.max_num_seqs:
                break
            if not self.block_manager.can_swap_in(r.request_id):
                break  # device blocks free up as others finish
            host, dev = self.block_manager.swap_in(r.request_id)
            self.kv_swapper.copy_in(r, host, dev)
            self.swapped.remove(r)
            r.swap_in()
            restored.append(r)
            self.num_swap_ins += 1
        self.running.extend(restored)
        return restored

    # -- the per-iteration decision --------------------------------------
    def schedule(self) -> ScheduledBatch:
        # TTL sweep, then restore swapped requests while blocks allow
        # (they already consumed compute; finishing them frees host AND
        # device memory fastest, and their sort keys predate anything
        # still waiting), then the batch.
        expired = self.expire_deadlines()
        swapped_in = self._swap_in_ready()
        return self._schedule_mixed(expired, swapped_in)

    def _claim_with_relief(self, req: Request, claim):
        """Run a block claim, retrying after each successful tier-relief
        demotion (tiered engines only; the claim raises BEFORE taking
        anything, so a retry never double-claims). Re-raises the final
        NoFreeBlocksError when relief is absent or dry."""
        while True:
            try:
                return claim()
            except NoFreeBlocksError:
                if self.tier_relief is None or not self.tier_relief(req):
                    raise

    def _admit_with_relief(self, req: Request, n: int,
                           claim) -> Optional[int]:
        """Admission-time claim for an n-token chunk: ``claim(n)`` must
        raise NoFreeBlocksError without taking anything. Tiered engines
        additionally SHRINK the chunk when even relief cannot make the
        whole thing fit the device pool — a request whose full context
        exceeds device HBM admits with whatever fits and grows through
        the mid-prefill pass, demoting its own cold prefix as it goes.
        Returns the chunk size that fit, or None."""
        while True:
            try:
                self._claim_with_relief(req, lambda: claim(n))
                return n
            except NoFreeBlocksError:
                if self.tier_relief is None or n <= 1:
                    return None
                n = max(1, n // 2)

    # -- the mixed batch ---------------------------------------------------
    def _schedule_mixed(self, expired: List[Request],
                        swapped_in: List[Request]) -> ScheduledBatch:
        """One MIXED batch under a raw token budget: (A) decode rows
        first — one token each, bounding TPOT; (B) mid-prefill rows
        continue with whatever budget remains, chunked; (C) new
        admissions fill the rest, their prompts chunked too (and served
        from the prefix cache where full prompt blocks match). Each pass
        runs the same evict-lowest-priority OOM loop, which is what the
        starvation guard rests on."""
        bm = self.block_manager
        budget = self.config.max_batched_tokens
        rows: List[Request] = []
        nsched: List[int] = []
        preempted: List[Request] = []
        evicted = set()   # ``preempted`` as a set: requests hash by identity
        used = 0
        any_prefill = False
        any_decode = False

        # a model with recurrent state under the prefix cache: a prompt
        # chunk that does not reach the prompt's end is cut to end on a
        # block boundary, where the engine snapshots the state (at most
        # block_size - 1 rows of budget given up; a chunk shorter than
        # the way to the next boundary runs as it is)
        align = bm.block_size if bm.state_snapshots else 0

        def cut(req: Request, start: int, n: int) -> int:
            if align and start + n < len(req.prompt_ids):
                return max((start + n) // align * align - start, 0) or n
            return n

        def drop_row(victim: Request):
            nonlocal used
            if victim in rows:
                i = rows.index(victim)
                rows.pop(i)
                used -= nsched.pop(i)

        def claim_slots(req: Request, new_len: int,
                        write_from: int) -> bool:
            """append_slot with the preempt-or-self-evict loop; False
            means req itself was evicted."""
            while True:
                try:
                    bm.append_slot(req.request_id, new_len,
                                   write_from=write_from)
                    return True
                except NoFreeBlocksError:
                    if self.tier_relief is not None \
                            and self.tier_relief(req):
                        continue  # demoted cold content freed room
                    victim = self._preempt_one(req)
                    if victim is None:
                        self._evict(req)
                        preempted.append(req)
                        evicted.add(req)
                        return False
                    preempted.append(victim)
                    evicted.add(victim)
                    drop_row(victim)

        # pass A — decode rows (fully caught-up requests; cost 1 each,
        # or 1+d for a speculative verify row carrying d draft tokens —
        # all-or-nothing: a verify that doesn't fit the budget sheds its
        # drafts and decodes plainly rather than verifying a partial
        # draft)
        decode_rows: List[Request] = []
        chunk_rows: List[Request] = []
        for r in sorted(self.running, key=lambda r: r.sort_key):
            (decode_rows if len(r.tokens) - r.num_cached == 1
             and r.num_generated > 0 else chunk_rows).append(r)
        for req in decode_rows:
            if req in evicted:
                continue  # evicted saving a more important row
            if used >= budget:
                break
            d = len(req.draft_tokens)
            if d and used + 1 + d > budget:
                req.draft_tokens = []
                d = 0
            if claim_slots(req, len(req.tokens) + d,
                           len(req.tokens) - 1):
                rows.append(req)
                nsched.append(1 + d)
                used += 1 + d
                any_decode = True

        # pass B — continue mid-prefill rows (chunk = remaining budget);
        # a preempted/recomputed request catching back up is the same
        # shape: everything in ``tokens`` past ``num_cached`` is prefill
        for req in chunk_rows:
            if req in evicted:
                continue
            left = budget - used
            if left <= 0:
                break
            total = len(req.tokens)
            remaining = total - req.num_cached
            n = cut(req, req.num_cached, min(remaining, left))
            if claim_slots(req, req.num_cached + n, req.num_cached):
                rows.append(req)
                nsched.append(n)
                used += n
                any_prefill = True
                if n < remaining:
                    req.was_chunked = True
                if req.was_chunked:
                    self.num_prefill_chunks += 1

        # pass C — admit waiting requests (priority, then FCFS);
        # head-of-line: the first candidate that doesn't fit ends
        # admission so a starved high-priority request is never overtaken
        admitted: List[Request] = []
        for req in sorted(self.waiting, key=lambda r: r.sort_key):
            if len(self.running) + len(admitted) >= \
                    self.config.max_num_seqs:
                break
            left = budget - used
            if left <= 0:
                break
            total = len(req.tokens)
            if bm.has_table(req.request_id):
                # fleet KV-ship continuation: its blocks were claimed
                # and filled at import, so admission is purely a seat +
                # budget decision; growth past the imported coverage
                # goes through the ordinary slot claim
                n = self._admit_with_relief(
                    req, min(total - req.num_cached, left),
                    lambda k: bm.append_slot(
                        req.request_id, req.num_cached + k,
                        write_from=req.num_cached))
                if n is None:
                    break  # blocks free up as running requests finish
                req.status = RequestStatus.RUNNING
                self.num_continuation_resumes += 1
                admitted.append(req)
                rows.append(req)
                nsched.append(n)
                used += n
                any_prefill = True
                if n < total - req.num_cached:
                    req.was_chunked = True
                if req.was_chunked:
                    self.num_prefill_chunks += 1
                continue
            hit = bm.match_prefix(req.tokens)
            eff = min(hit, total - 1)
            n = self._admit_with_relief(
                req, cut(req, eff, min(total - eff, left)),
                lambda k: bm.allocate(req.request_id, eff + k,
                                      tokens=req.tokens))
            if n is None:
                break  # blocks free up as running requests finish
            req.num_cached = bm.last_hit_tokens
            self.num_admitted_prompt_tokens += len(req.prompt_ids)
            req.status = RequestStatus.RUNNING
            admitted.append(req)
            rows.append(req)
            nsched.append(n)
            used += n
            any_prefill = True
            if n < total - req.num_cached:
                req.was_chunked = True
            if req.was_chunked:
                self.num_prefill_chunks += 1
        if admitted:
            taken = set(id(r) for r in admitted)
            self.waiting = deque(r for r in self.waiting
                                 if id(r) not in taken)
            self.running.extend(admitted)

        if not rows:
            return ScheduledBatch(kind="idle", preempted=preempted,
                                  swapped_in=swapped_in, expired=expired)
        kind = ("mixed" if (any_prefill and any_decode)
                else "prefill" if any_prefill else "decode")
        return ScheduledBatch(kind=kind, requests=rows,
                              preempted=preempted, swapped_in=swapped_in,
                              expired=expired, num_scheduled=nsched)
