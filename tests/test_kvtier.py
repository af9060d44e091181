"""Tiered KV subsystem pins (ISSUE 19).

Layers, cheapest first:

* :class:`KVTiersConfig` parsing/validation and the engine-side config
  guard (tiering without the trie is a contradiction);
* BlockManager tier mechanics — virtual host entries, the ordered
  demote/promote move ledger, chain demote (slots park cached-free and
  UNOWNED), chain evict, exact invariants throughout;
* over-device-pool serving: one request whose context exceeds device
  HBM completes greedy- AND sampled-token-identical to an
  unconstrained single-engine reference — demotion instead of
  eviction, promotion instead of recompute;
* session park/resume: a multi-turn continuation re-prefills ZERO
  prompt tokens (counter-asserted), partial-tail bytes restore, a
  diverged prompt is a clean refusal that keeps the session;
* fleet: router park/resume with holder affinity, the host-pressure
  offload over the prefix ticket ladder (exactly one counted outcome
  per issued ticket), and a dead holder degrading resume to recompute
  — never loss, never duplication;
* the randomized tier-migration storm: interleaved demote / promote /
  park / resume / abort / peer-fault waves with pool invariants
  checked per wave and full greedy+sampled parity at the end.
"""
import itertools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.block_manager import BlockManager
from paddle_tpu.serving.fleet import (
    FleetConfig, FleetRouter, InProcessReplica,
)
from paddle_tpu.serving.kvtier import KVTiersConfig, TieredKVStore
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _no_fault_leak():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    return model


def _ecfg(**kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 8)
    kw.setdefault("max_model_len", 96)
    kw.setdefault("drain_grace_s", 0.0)
    return EngineConfig(**kw)


def _tiered_cfg(**kw):
    kw.setdefault("kv_tiers", True)
    return _ecfg(**kw)


def _run(eng, max_steps=600):
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < max_steps
    if eng._kvtier is not None:
        eng._kvtier.apply_moves()
    eng.block_manager.check_invariants()


def _drain_router(router, max_steps=400):
    outs = []
    for _ in range(max_steps):
        if not router.has_unfinished():
            return outs
        outs.extend(router.step())
    raise AssertionError("router failed to converge")


def _reference(model, prompts_by_rid, cfg=None):
    """Unconstrained single-engine oracle: big device pool, no tiers.
    Request ids matter — the sampling stream seeds from the id."""
    eng = LLMEngine(model, cfg or _ecfg(num_blocks=256))
    for rid, (prompt, sp) in prompts_by_rid.items():
        eng.add_request(rid, prompt, sampling=sp)
    _run(eng)
    return {rid: list(eng.get_request(rid).generated)
            for rid in prompts_by_rid}


GREEDY = SamplingParams(max_new_tokens=8)
SAMPLED = SamplingParams(max_new_tokens=8, temperature=0.8, top_k=20,
                         seed=7)


# ---------------------------------------------------------------------------
# config + guards
# ---------------------------------------------------------------------------

class TestTiersConfig:
    def test_from_any_forms(self):
        assert KVTiersConfig.from_any(None) is None
        assert KVTiersConfig.from_any(False) is None
        cfg = KVTiersConfig.from_any(True)
        assert isinstance(cfg, KVTiersConfig)
        cfg = KVTiersConfig.from_any({"num_host_blocks": 12,
                                      "host_watermark": 0.5})
        assert cfg.num_host_blocks == 12
        assert cfg.host_watermark == 0.5
        same = KVTiersConfig(max_sessions=3)
        assert KVTiersConfig.from_any(same) is same
        with pytest.raises(ValueError):
            KVTiersConfig.from_any("yes")

    def test_validation(self):
        with pytest.raises(ValueError):
            KVTiersConfig(host_watermark=1.5)
        with pytest.raises(ValueError):
            KVTiersConfig(num_host_blocks=0)
        with pytest.raises(ValueError):
            KVTiersConfig(max_sessions=0)

    def test_tiers_require_prefix_cache(self, tiny_model):
        with pytest.raises(ValueError, match="prefix"):
            LLMEngine(tiny_model, _tiered_cfg(prefix_cache=False))

    def test_tiers_force_host_pool(self, tiny_model):
        eng = LLMEngine(tiny_model, _tiered_cfg(num_blocks=8))
        assert eng.cfg.num_host_blocks >= eng.cfg.num_blocks
        assert eng.block_manager.reachable_blocks > eng.cfg.num_blocks


# ---------------------------------------------------------------------------
# BlockManager tier mechanics
# ---------------------------------------------------------------------------

def _commit_chain(bm, rid, tokens):
    bm.allocate(rid, len(tokens), tokens=tokens)
    bm.commit_prefix(rid, tokens, len(tokens))


class TestTierMechanics:
    def _bm(self, **kw):
        kw.setdefault("num_blocks", 8)
        kw.setdefault("block_size", 4)
        kw.setdefault("num_host_blocks", 8)
        kw.setdefault("enable_prefix_cache", True)
        kw.setdefault("tiered", True)
        return BlockManager(**kw)

    def test_demote_cached_free_moves_cold_end(self):
        bm = self._bm()
        tokens = list(range(16))
        _commit_chain(bm, "r0", tokens)
        bm.free("r0")
        bm.check_invariants()
        free_before = bm.num_uncached_free_blocks
        got = bm.demote_cached_free(2)
        assert got == 2
        moves = bm.take_tier_moves()
        assert [m[0] for m in moves] == ["demote", "demote"]
        assert bm.num_demotes == 2
        assert bm.num_uncached_free_blocks == free_before + 2
        # content stayed discoverable: a fresh allocate shares it, with
        # the shared entries now naming HOST slots (virtual ids)
        table = bm.allocate("r1", 16, tokens=tokens)
        assert bm.last_hit_tokens > 0
        assert any(bm.is_host_entry(e) for e in table)
        # the capped full-match hit COWs the shared tail block, and a
        # COW off a host-tier source records a promote — drain it
        bm.take_tier_moves()
        bm.check_invariants()

    def test_promote_blocks_round_trip(self):
        bm = self._bm()
        tokens = list(range(16))
        _commit_chain(bm, "r0", tokens)
        bm.free("r0")
        assert bm.demote_cached_free(4) == 4
        bm.take_tier_moves()
        table = bm.allocate("r1", 16, tokens=tokens)
        virt = [e for e in table if bm.is_host_entry(e)]
        assert virt
        # the allocate above already promoted once (capped-hit COW off
        # the shared host tail) — assert the DELTA from promote_blocks
        before = bm.num_promotes
        promoted = bm.promote_blocks("r1", len(virt))
        assert promoted == len(virt)
        moves = bm.take_tier_moves()
        assert all(m[0] == "promote" for m in moves)
        assert bm.num_promotes - before == promoted
        assert not any(bm.is_host_entry(e) for e in
                       bm.block_table("r1"))
        bm.check_invariants()

    def test_demote_chain_parks_slots_unowned(self):
        bm = self._bm()
        tokens = list(range(16))
        _commit_chain(bm, "r0", tokens)
        bm.free("r0")
        demoted = bm.demote_chain(tokens, len(tokens))
        assert demoted == 4
        bm.take_tier_moves()
        # parked slots are cached-free: registered content, refcount 0,
        # still sitting in the host free list (capacity can reclaim)
        st = bm.host_tier_stats()
        assert st["registered"] == 4
        assert st["used"] == 0
        assert st["free"] == bm.num_host_blocks
        bm.check_invariants()
        # a shared resume bumps them to owned
        table, hit, tail = bm.resume_chain("r1", tokens + [99], 16,
                                           want_tail=False)
        assert hit == 16
        assert bm.host_tier_stats()["used"] == 4
        bm.check_invariants()

    def test_demote_chain_skips_referenced_blocks(self):
        bm = self._bm()
        tokens = list(range(16))
        _commit_chain(bm, "r0", tokens)  # still owned by r0
        assert bm.demote_chain(tokens, len(tokens)) == 0
        bm.check_invariants()

    def test_evict_chain_drops_both_tiers(self):
        bm = self._bm()
        tokens = list(range(16))
        _commit_chain(bm, "r0", tokens)
        bm.free("r0")
        bm.demote_chain(tokens, len(tokens))
        bm.take_tier_moves()
        dropped = bm.evict_chain(tokens, len(tokens))
        assert dropped == 4
        st = bm.host_tier_stats()
        assert st["registered"] == 0
        assert bm.match_prefix(tokens) == 0
        bm.check_invariants()

    def test_move_ledger_preserves_order(self):
        bm = self._bm(num_blocks=4, num_host_blocks=4)
        tokens = list(range(16))
        _commit_chain(bm, "r0", tokens)
        bm.free("r0")
        bm.demote_chain(tokens, len(tokens))
        # resume promotes into blocks the demote just vacated: the
        # ledger must replay demotes before the promotes that reuse
        # their source blocks
        table, hit, _ = bm.resume_chain("r1", tokens + [99], 16,
                                        want_tail=False)
        bm.promote_blocks("r1", 4)
        moves = bm.take_tier_moves()
        kinds = [m[0] for m in moves]
        assert kinds.index("promote") > kinds.index("demote")
        bm.check_invariants()


# ---------------------------------------------------------------------------
# over-device-pool serving
# ---------------------------------------------------------------------------

class TestOverPool:
    @pytest.mark.parametrize("sp", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    def test_context_exceeds_device_pool(self, tiny_model, sp):
        """40-token prompt + 12 new = 13 blocks against an 8-block
        device pool: admission counts reachable-tier blocks, the
        scheduler demotes the request's own cold prefix to make room,
        and the output is token-identical to an unconstrained run."""
        sp = SamplingParams(**{**sp.__dict__, "max_new_tokens": 12})
        rng = np.random.default_rng(3)
        prompt = [int(t) for t in rng.integers(0, 255, size=40)]
        eng = LLMEngine(tiny_model, _tiered_cfg(num_blocks=8))
        assert eng.block_manager.reachable_blocks >= 13
        eng.add_request("big", prompt, sampling=sp)
        _run(eng)
        got = list(eng.get_request("big").generated)
        assert eng.block_manager.num_demotes > 0
        ref = _reference(tiny_model, {"big": (prompt, sp)})
        assert got == ref["big"]

    def test_admission_rejects_past_reachable(self, tiny_model):
        eng = LLMEngine(tiny_model, _tiered_cfg(
            num_blocks=4, kv_tiers={"num_host_blocks": 4},
            max_model_len=96))
        rng = np.random.default_rng(4)
        prompt = [int(t) for t in rng.integers(0, 255, size=60)]
        # past reachable_blocks the request could never be served even
        # alone — the engine refuses at submission, not via an output
        with pytest.raises(ValueError, match="reachable"):
            eng.add_request("huge", prompt,
                            sampling=SamplingParams(max_new_tokens=30))


# ---------------------------------------------------------------------------
# session park / resume (single engine)
# ---------------------------------------------------------------------------

class TestParkResume:
    @pytest.mark.parametrize("sp", [GREEDY, SAMPLED],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("plen", [21, 22],
                             ids=["aligned-tail", "partial-tail"])
    def test_zero_prompt_recompute(self, tiny_model, sp, plen):
        rng = np.random.default_rng(plen)
        prompt = [int(t) for t in rng.integers(0, 255, size=plen)]
        eng = LLMEngine(tiny_model, _tiered_cfg(num_blocks=16))
        eng.add_request("turn1", prompt, sampling=sp)
        _run(eng)
        turn1 = list(eng.get_request("turn1").generated)
        eng.release_request("turn1")  # sessions survive release
        info = eng.park_session("turn1")
        assert info is not None and info["parked"]
        assert eng.park_session("turn1")["parked"]  # idempotent

        prompt2 = prompt + turn1 + [int(t) for t in
                                    rng.integers(0, 255, size=5)]
        hit = eng.resume_session("turn2", "turn1", prompt2, sampling=sp)
        assert hit == info["tokens_covered"]
        _run(eng)
        turn2 = list(eng.get_request("turn2").generated)
        kvt = eng._kvtier
        assert kvt.num_resume_recomputed_tokens == 0
        assert kvt.num_park_resumes == 1
        assert eng.metrics.snapshot()["serving_kv_tier_park_resumes"] \
            == 1
        ref = _reference(tiny_model, {"turn2": (prompt2, sp)})
        assert turn2 == ref["turn2"]

    def test_resume_mismatch_keeps_session(self, tiny_model):
        rng = np.random.default_rng(9)
        prompt = [int(t) for t in rng.integers(0, 255, size=12)]
        eng = LLMEngine(tiny_model, _tiered_cfg(num_blocks=16))
        eng.add_request("s", prompt, sampling=GREEDY)
        _run(eng)
        eng.park_session("s")
        bad = list(reversed(prompt)) + [1, 2, 3]
        with pytest.raises(ValueError, match="extend"):
            eng.resume_session("s2", "s", bad, sampling=GREEDY)
        assert eng.session_info("s") is not None  # not consumed

    def test_resume_after_eviction_recomputes(self, tiny_model):
        """The degradation floor: the parked chain was reclaimed for
        capacity — resume admits COLD (full re-prefill), counted, and
        still token-identical."""
        rng = np.random.default_rng(10)
        prompt = [int(t) for t in rng.integers(0, 255, size=16)]
        eng = LLMEngine(tiny_model, _tiered_cfg(num_blocks=16))
        eng.add_request("s", prompt, sampling=GREEDY)
        _run(eng)
        turn1 = list(eng.get_request("s").generated)
        eng.park_session("s")
        # reclaim the chain out from under the park
        rec = eng._kvtier.sessions["s"]
        eng.block_manager.evict_chain(rec.tokens, rec.covered)
        prompt2 = prompt + turn1 + [5, 6, 7]
        hit = eng.resume_session("s2", "s", prompt2, sampling=GREEDY)
        assert hit == 0
        _run(eng)
        assert eng._kvtier.num_resume_recomputes == 1
        assert eng._kvtier.num_resume_recomputed_tokens > 0
        # both counters are part of the stats() vocabulary (they were
        # bumped-but-never-read before PR 20's drift linter)
        stats = eng.tier_stats()
        assert stats["resume_recomputes"] == 1
        assert stats["resume_recomputed_tokens"] > 0
        ref = _reference(tiny_model, {"s2": (prompt2, GREEDY)})
        assert list(eng.get_request("s2").generated) == ref["s2"]

    def test_torn_tail_restore_frees_resumed_claim(self, tiny_model):
        """A tail restore that dies mid-copy must free the whole
        resumed chain claim (the leaked-resource-on-raise class this
        PR's linter flags) while keeping the session record, so the
        SAME resume retries cleanly."""
        rng = np.random.default_rng(13)
        prompt = [int(t) for t in rng.integers(0, 255, size=22)]
        eng = LLMEngine(tiny_model, _tiered_cfg(num_blocks=16))
        eng.add_request("s", prompt, sampling=GREEDY)
        _run(eng)
        turn1 = list(eng.get_request("s").generated)
        eng.release_request("s")
        info = eng.park_session("s")
        assert info is not None and info["parked"]
        prompt2 = prompt + turn1 + [1, 2, 3]
        def torn(*a):
            raise RuntimeError("torn tail copy")
        eng._pin_caches = torn          # dies inside the tail restore
        try:
            with pytest.raises(RuntimeError, match="torn tail copy"):
                eng.resume_session("s2", "s", prompt2, sampling=GREEDY)
        finally:
            del eng._pin_caches         # back to the class method
        bm = eng.block_manager
        assert not bm.has_table("s2")     # the claim did not strand
        bm.check_invariants()
        assert eng.session_info("s") is not None  # kept for the retry
        hit = eng.resume_session("s2", "s", prompt2, sampling=GREEDY)
        assert hit == info["tokens_covered"]
        _run(eng)
        ref = _reference(tiny_model, {"s2": (prompt2, GREEDY)})
        assert list(eng.get_request("s2").generated) == ref["s2"]

    def test_session_bound(self, tiny_model):
        eng = LLMEngine(tiny_model, _tiered_cfg(
            num_blocks=32, kv_tiers={"max_sessions": 2}))
        rng = np.random.default_rng(11)
        for i in range(3):
            p = [int(t) for t in rng.integers(0, 255, size=8)]
            eng.add_request(f"s{i}", p, sampling=GREEDY)
            _run(eng)
        kvt = eng._kvtier
        assert len(kvt.sessions) == 2
        assert "s0" not in kvt.sessions  # oldest out

    def test_untired_engine_refuses_sessions(self, tiny_model):
        eng = LLMEngine(tiny_model, _ecfg())
        with pytest.raises(ValueError, match="kv_tiers"):
            eng.park_session("nope")
        assert eng.tier_stats() is None


# ---------------------------------------------------------------------------
# fleet: park / resume / offload / holder death
# ---------------------------------------------------------------------------

def _fleet(model, n=2, fcfg=None, peers=False, **ekw):
    reps = [InProcessReplica(model, _tiered_cfg(**ekw),
                             replica_id=f"rep{i}") for i in range(n)]
    if peers:
        for r in reps:
            r.start_peer()
    return reps, FleetRouter(reps, fcfg or FleetConfig())


class TestFleetSessions:
    def test_park_resume_holder_affinity(self, tiny_model):
        reps, router = _fleet(tiny_model, num_blocks=16)
        rng = np.random.default_rng(20)
        prompt = [int(t) for t in rng.integers(0, 255, size=21)]
        rid = router.add_request("t1", prompt, sampling=GREEDY)
        _drain_router(router)
        fr = router.get_request(rid)
        turn1, holder = list(fr.generated), fr.replica_id
        assert router.park_session(rid) is not None
        heng = next(r for r in reps
                    if r.replica_id == holder).engine
        prompt2 = prompt + turn1 + [1, 2, 3, 4, 5]
        rid2 = router.resume_session(rid, prompt2, sampling=GREEDY)
        _drain_router(router)
        fr2 = router.get_request(rid2)
        assert fr2.replica_id == holder  # affinity beat load balance
        assert router.num_session_resumes == 1
        assert router.num_session_resume_recomputes == 0
        assert heng._kvtier.num_resume_recomputed_tokens == 0
        ref = _reference(tiny_model, {rid2: (prompt2, GREEDY)})
        assert list(fr2.generated) == ref[rid2]
        snap = router.snapshot()
        assert snap["fleet_session_parks"] == 1
        assert snap["fleet_session_resumes"] == 1

    def test_offload_past_watermark(self, tiny_model):
        reps, router = _fleet(
            tiny_model, peers=True,
            fcfg=FleetConfig(tier_offload_watermark=1e-6),
            num_blocks=16)
        rng = np.random.default_rng(21)
        prompt = [int(t) for t in rng.integers(0, 255, size=21)]
        rid = router.add_request("sess", prompt, sampling=GREEDY)
        _drain_router(router)
        fr = router.get_request(rid)
        turn1, holder = list(fr.generated), fr.replica_id
        src = next(r for r in reps if r.replica_id == holder)
        dst = next(r for r in reps if r.replica_id != holder)
        assert router.park_session(rid) is not None
        router.step()  # offload sweep fires past the watermark
        assert router.num_session_offloads == 1
        assert router._sessions[rid]["holder"] == dst.replica_id
        assert src.engine.session_info(rid) is None
        assert dst.engine.session_info(rid) is not None
        assert src.engine.tier_stats()["peer_blocks"] > 0
        # ticket partition stays exact through the prefix-ladder ship
        assert sum(router.ticket_outcomes.values()) \
            == router.num_tickets_issued
        prompt2 = prompt + turn1 + [9, 8, 7]
        rid2 = router.resume_session(rid, prompt2, sampling=GREEDY)
        _drain_router(router)
        fr2 = router.get_request(rid2)
        assert fr2.replica_id == dst.replica_id
        assert dst.engine._kvtier.num_resume_recomputed_tokens == 0
        ref = _reference(tiny_model, {rid2: (prompt2, GREEDY)})
        assert list(fr2.generated) == ref[rid2]
        for r in reps:
            r.close_peer()

    def test_dead_holder_degrades_to_recompute(self, tiny_model):
        reps, router = _fleet(tiny_model, num_blocks=16)
        rng = np.random.default_rng(22)
        prompt = [int(t) for t in rng.integers(0, 255, size=21)]
        rid = router.add_request("t1", prompt, sampling=GREEDY)
        _drain_router(router)
        fr = router.get_request(rid)
        turn1, holder = list(fr.generated), fr.replica_id
        assert router.park_session(rid) is not None
        router.kill_replica(holder, "fault")
        assert rid not in router._sessions  # pruned with the corpse
        prompt2 = prompt + turn1 + [4, 4, 4]
        rid2 = router.resume_session(rid, prompt2, sampling=GREEDY)
        _drain_router(router)
        fr2 = router.get_request(rid2)
        assert fr2.finish_reason in ("stop", "length")
        assert fr2.replica_id != holder
        assert router.num_session_resumes == 0
        assert router.num_session_resume_recomputes == 1
        ref = _reference(tiny_model, {rid2: (prompt2, GREEDY)})
        assert list(fr2.generated) == ref[rid2]


# ---------------------------------------------------------------------------
# randomized tier-migration storm
# ---------------------------------------------------------------------------

class TestMigrationStorm:
    def test_storm(self, tiny_model):
        rng = np.random.default_rng(42)
        reps, router = _fleet(
            tiny_model, peers=True,
            fcfg=FleetConfig(tier_offload_watermark=0.05),
            num_blocks=16, max_num_seqs=4)
        seq = itertools.count()
        expectations = {}   # rid -> (prompt, sampling)
        finished = {}       # rid -> generated tokens
        aborted = set()
        resumable = []      # finished rids not yet resumed

        def sp_for():
            if rng.random() < 0.5:
                return GREEDY
            return SamplingParams(max_new_tokens=8, temperature=0.8,
                                  top_k=20, seed=int(rng.integers(1e6)))

        def absorb(outs):
            for o in outs:
                if o.finished and o.finish_reason in ("stop", "length"):
                    finished[o.request_id] = list(o.generated)
                    resumable.append(o.request_id)

        def check_wave():
            for r in reps:
                if r.alive and r.engine._kvtier is not None:
                    r.engine._kvtier.apply_moves()
                    r.engine.block_manager.check_invariants()
            assert sum(router.ticket_outcomes.values()) \
                == router.num_tickets_issued

        for wave in range(4):
            for _ in range(int(rng.integers(2, 5))):
                sp = sp_for()
                if resumable and rng.random() < 0.5:
                    sid = resumable.pop(int(rng.integers(
                        len(resumable))))
                    base = expectations[sid][0] + finished[sid]
                    prompt = base + [int(t) for t in rng.integers(
                        0, 255, size=int(rng.integers(3, 8)))]
                    if rng.random() < 0.7:
                        router.park_session(sid)
                    rid = router.resume_session(sid, prompt,
                                                sampling=sp)
                else:
                    prompt = [int(t) for t in rng.integers(
                        0, 255, size=int(rng.integers(8, 30)))]
                    rid = router.add_request(f"storm-{next(seq)}",
                                             prompt, sampling=sp)
                expectations[rid] = (prompt, sp)
            if rng.random() < 0.4:
                # one peer-plane fault for this wave: offload ships
                # degrade a rung, never lose the session
                faults.install("fleet.peer_connect_fail:flag*1")
            for _ in range(int(rng.integers(2, 6))):
                absorb(router.step())
                open_rids = list(router._open)
                if open_rids and rng.random() < 0.15:
                    victim = open_rids[int(rng.integers(
                        len(open_rids)))]
                    router.abort_request(victim)
                    aborted.add(victim)
            faults.clear()
            check_wave()

        absorb(_drain_router(router))
        check_wave()

        todo = {rid: expectations[rid] for rid in finished
                if rid not in aborted}
        assert len(todo) >= 6  # the storm actually exercised traffic
        ref = _reference(tiny_model, todo)
        for rid in todo:
            assert finished[rid] == ref[rid], rid
        for r in reps:
            r.close_peer()
