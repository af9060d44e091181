"""Out-of-process fleet transport pins (ISSUE 12).

Three layers, cheapest first:

* protocol units — framing, deadlines, idempotent retry, late-reply
  hygiene, error mapping, the ``fleet.rpc_delay``/``fleet.rpc_drop``
  fault points;
* **loopback** tests — a real :class:`RpcClient` talking to a real
  :class:`ReplicaServicer` over a socketpair, with the servicer thread
  hosting a real tiny-Llama engine in-process. "SIGKILL" here is an
  abrupt server-side socket sever with no farewell frame — byte-for-
  byte what the client observes when the worker process is killed —
  which makes the headline pin (mid-decode kill resumes bit-identical,
  greedy AND sampled) runnable in the non-slow tier. The true
  multiprocess versions live in test_fleet_subprocess.py (slow);
* router bookkeeping regressions — hand-off budget consumed exactly
  once per death, the ``handoff_exhausted`` counter, dead-handle abort
  hygiene — and the registry's skew-immune monotonic liveness.
"""
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.replica_registry import MemStore, ReplicaRegistry
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    EngineConfig, LLMEngine, RequestOutput, SamplingParams,
)
from paddle_tpu.serving.fleet import (
    FleetConfig, FleetRouter, InProcessReplica, ReplicaGone,
    ReplicaHandle, ReplicaLoad, ReplicaServicer, RpcClient,
    RpcRemoteError, RpcTimeout, SubprocessReplica,
)
from paddle_tpu.serving.fleet.transport import (
    IDEMPOTENT_METHODS, MUTATION_METHODS, RpcError, recv_frame,
    send_frame,
)
from paddle_tpu.serving.request import FINISH_REASONS
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _no_fault_leak():
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------
class TestFraming:
    def test_roundtrip_and_eof(self):
        a, b = socket.socketpair()
        send_frame(a, {"id": 1, "method": "ping", "params": {}})
        send_frame(a, {"id": 2, "x": [1, 2, 3]})
        assert recv_frame(b) == {"id": 1, "method": "ping", "params": {}}
        assert recv_frame(b) == {"id": 2, "x": [1, 2, 3]}
        a.close()
        assert recv_frame(b) is None       # clean EOF
        b.close()

    def test_oversized_length_prefix_is_connection_loss(self):
        a, b = socket.socketpair()
        a.sendall(b"\xff\xff\xff\xff")     # 4 GiB frame: garbage
        with pytest.raises(OSError):
            recv_frame(b)
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# RpcClient semantics against a hand-rolled server
# ---------------------------------------------------------------------------
def _server(sock, script):
    """Serve frames per `script(msg) -> reply | None (swallow)`."""

    def run():
        try:
            while True:
                msg = recv_frame(sock)
                if msg is None:
                    return
                reply = script(msg)
                if reply is not None:
                    send_frame(sock, reply)
        except OSError:
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


class TestRpcClient:
    def _client(self, script, **kw):
        a, b = socket.socketpair()
        _server(b, script)
        kw.setdefault("backoff_base_s", 0.01)
        return RpcClient(a, **kw), b

    def test_call_response_matched_by_id(self):
        cl, _ = self._client(
            lambda m: {"id": m["id"], "ok": True,
                       "result": m["params"]["x"] * 2})
        # "double" is a test-only verb outside the fleet partition, so
        # it must be classified explicitly at the call site
        assert cl.call("double", {"x": 21}, idempotent=True) == 42
        assert cl.call("double", {"x": 3}, idempotent=True) == 6
        assert cl.stats["calls"] == 2
        cl.close()

    def test_unclassified_verb_raises_not_defaults(self):
        """PR 19 shipped tier_stats dispatched but classified nowhere —
        it silently became a non-retried mutation. Now an unclassified
        verb refuses to pick a retry policy at all."""
        cl, _ = self._client(
            lambda m: {"id": m["id"], "ok": True, "result": 1})
        with pytest.raises(RpcError, match="neither"):
            cl.call("double", {"x": 1})
        # explicit classification and partitioned verbs still work
        assert cl.call("tier_stats", {}) == 1   # now IDEMPOTENT
        assert "tier_stats" in IDEMPOTENT_METHODS
        assert IDEMPOTENT_METHODS.isdisjoint(MUTATION_METHODS)
        cl.close()

    def test_mutation_timeout_no_retry(self):
        calls = []

        def swallow(m):
            calls.append(m["method"])
            return None

        cl, _ = self._client(swallow)
        with pytest.raises(RpcTimeout):
            cl.call("step", {}, deadline_s=0.1, idempotent=False)
        time.sleep(0.05)
        assert calls == ["step"]          # exactly one attempt
        assert cl.stats["timeouts"] == 1
        cl.close()

    def test_idempotent_retries_with_backoff_then_succeeds(self):
        seen = []

        def flaky(m):
            seen.append(m["id"])
            if len(seen) == 1:
                return None               # lose the first reply
            return {"id": m["id"], "ok": True, "result": "pong"}

        cl, _ = self._client(flaky)
        assert cl.call("ping", {}, deadline_s=0.15) == "pong"
        assert cl.stats["retries"] == 1
        assert seen[0] != seen[1]         # the retry is a NEW sequence
        cl.close()

    def test_late_reply_to_abandoned_call_never_poisons_next(self):
        def script(m):
            if m["method"] == "slow":
                # reply AFTER the caller's deadline has expired
                time.sleep(0.25)
                return {"id": m["id"], "ok": True, "result": "stale"}
            return {"id": m["id"], "ok": True, "result": "fresh"}

        cl, _ = self._client(script)
        with pytest.raises(RpcTimeout):
            cl.call("slow", {}, deadline_s=0.05, idempotent=False)
        # the stale reply lands while this call is pending; ids differ
        assert cl.call("fast", {}, deadline_s=2.0,
                       idempotent=False) == "fresh"
        cl.close()

    def test_eof_mid_call_raises_replica_gone_not_timeout(self):
        def die(m):
            raise OSError("boom")          # server loop exits, EOF

        cl, srv = self._client(die)
        srv.shutdown(socket.SHUT_RDWR)
        srv.close()
        time.sleep(0.05)
        with pytest.raises(ReplicaGone):
            cl.call("step", {}, deadline_s=5.0, idempotent=False)
        assert cl.closed
        cl.close()

    def test_remote_error_mapping(self):
        stub = _StubReplica()
        svc = ReplicaServicer(stub)
        assert svc.handle({"id": 1, "method": "nope", "params": {}})[
            "ok"] is False
        cl, _ = self._client(ReplicaServicer(stub).handle)
        with pytest.raises(ValueError):   # known types cross as themselves
            cl.call("add_request", {
                "request_id": "r", "prompt_ids": [],
                "sampling": {"max_new_tokens": 0}}, idempotent=False)
        with pytest.raises(RpcRemoteError):
            cl.call("no_such_verb", {}, idempotent=False)
        cl.close()

    def test_rpc_drop_fault_mutation_dies_query_retries(self):
        cl, _ = self._client(
            lambda m: {"id": m["id"], "ok": True, "result": "pong"})
        with faults.injected("fleet.rpc_drop:flag*1"):
            with pytest.raises(RpcTimeout):   # mutation: one lost frame
                cl.call("step", {}, deadline_s=1.0, idempotent=False)
        with faults.injected("fleet.rpc_drop:flag*1"):
            # idempotent: the retry re-sends and succeeds
            assert cl.call("ping", {}, deadline_s=1.0) == "pong"
            assert cl.stats["retries"] >= 1
        cl.close()

    def test_rpc_delay_fault_adds_latency(self):
        cl, _ = self._client(
            lambda m: {"id": m["id"], "ok": True, "result": 1})
        with faults.injected("fleet.rpc_delay:sleep:0.2*1"):
            t0 = time.monotonic()
            assert cl.call("load", {}) == 1
            assert time.monotonic() - t0 >= 0.2
        cl.close()


class _StubReplica(ReplicaHandle):
    """Minimal servicer target for protocol-level tests."""

    def __init__(self):
        self.replica_id = "stub"
        self.alive = True
        self.retiring = False

    def admission_verdict(self, prompt_tokens):
        return None

    def estimated_ttft_ms(self, prompt_tokens):
        return 1.0

    def load(self):
        return ReplicaLoad()

    @property
    def is_draining(self):
        return False

    @property
    def drained(self):
        return False

    def has_unfinished(self):
        return False

    def add_request(self, request_id, prompt_ids, sampling, *,
                    rng_state=None):
        pass  # SamplingParams(max_new_tokens=0) raises before this

    def abort_request(self, request_id):
        return False

    def release_request(self, request_id):
        pass

    def rng_state(self, request_id):
        return None

    def step(self):
        return []

    def start_drain(self, reason="manual"):
        return []


# ---------------------------------------------------------------------------
# loopback: real engine behind a real socket; sever() == SIGKILL as the
# client sees it
# ---------------------------------------------------------------------------
class Loopback:
    def __init__(self, inner, client_kw=None):
        self.inner = inner
        a, b = socket.socketpair()
        self._server_sock = b
        threading.Thread(target=ReplicaServicer(inner).serve, args=(b,),
                         daemon=True).start()
        self.client = RpcClient(a, name=inner.replica_id,
                                **(client_kw or {}))
        self.handle = SubprocessReplica(inner.replica_id, self.client)
        # fleet.worker_kill's SIGKILL, loopback edition: the server
        # half vanishes abruptly — no farewell frame, replies in flight
        # lost — exactly the byte stream a killed process leaves behind
        self.handle.hard_kill = self.sever

    def sever(self):
        try:
            self._server_sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._server_sock.close()


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    return model


def _ecfg(**kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("drain_grace_s", 0.0)
    return EngineConfig(**kw)


def _prompts(model, n, seed=7):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, model.config.vocab_size,
                                       size=3 + i % 4)))
            for i in range(n)]


def _reference(model, prompts, sp, ids):
    eng = LLMEngine(model, _ecfg())
    for rid, p in zip(ids, prompts):
        eng.add_request(rid, p, sampling=sp)
    while eng.has_unfinished():
        eng.step()
    return {rid: list(eng.get_request(rid).generated) for rid in ids}


def _drain_router(router, max_steps=300):
    outs = []
    for _ in range(max_steps):
        if not router.has_unfinished():
            return outs
        outs.extend(router.step())
    raise AssertionError("router failed to converge")


def _sp(sampled):
    if sampled:
        return SamplingParams(max_new_tokens=8, temperature=0.8,
                              top_p=0.9)
    return SamplingParams(max_new_tokens=8)


class TestLoopbackE2E:
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_generate_over_transport_matches_engine(self, tiny_model,
                                                    sampled):
        sp = _sp(sampled)
        prompts = _prompts(tiny_model, 3)
        ids = [f"t{i}" for i in range(3)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                       replica_id="L0"))
        router = FleetRouter([lb.handle])
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert all(final[r].finish_reason == "length" for r in ids)

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_sigkill_mid_decode_resumes_bit_identical(self, tiny_model,
                                                      sampled):
        # THE pin: the worker dies with no warning mid-decode; every
        # in-flight request resumes on the peer and the client-visible
        # token streams are bit-identical to an uninterrupted single
        # engine — for sampling, from the piggybacked composite
        # rng_state (the dead worker can't be queried post-mortem).
        sp = _sp(sampled)
        prompts = _prompts(tiny_model, 6)
        ids = [f"k{i}" for i in range(6)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb0 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                        replica_id="L0"))
        lb1 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                        replica_id="L1"))
        router = FleetRouter([lb0.handle, lb1.handle])
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        faults.install("fleet.worker_kill:flag:L0@3*1")
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert all(final[r].finish_reason == "length" for r in ids)
        assert not lb0.handle.alive
        assert router.num_handoffs >= 1
        assert router.num_replicas_dead == 1
        # exactly-once emission: every token reached the client once
        counts = {}
        for o in outs:
            if o.token is not None:
                counts[o.request_id] = counts.get(o.request_id, 0) + 1
        assert counts == {r: len(ref[r]) for r in ids}

    def test_drain_over_transport_hands_off_bit_identical(self,
                                                          tiny_model):
        # SIGTERM path through the wire: start_drain's reply carries
        # the aborts AND their rng states in one frame
        sp = _sp(True)
        prompts = _prompts(tiny_model, 4)
        ids = [f"d{i}" for i in range(4)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb0 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                        replica_id="L0"))
        lb1 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                        replica_id="L1"))
        router = FleetRouter([lb0.handle, lb1.handle])
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        for _ in range(3):
            router.step()
        router.retire_replica(lb0.handle)
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert lb0.handle.replica_id not in [
            h.replica_id for h in router.replicas]  # reaped after drain

    def test_chaos_storm_no_strands_no_dups_pools_full(self, tiny_model):
        # randomized kill/drop/delay interleaving (schedule drawn from
        # a seeded rng, two rounds). Invariants, not outcomes: every
        # request terminates with a FINISH_REASONS member, every token
        # reaches the client exactly once, and the surviving engines'
        # block pools drain back to full.
        for seed in (0, 1):
            sched = np.random.default_rng(seed)
            n = 8
            prompts = _prompts(tiny_model, n, seed=20 + seed)
            ids = [f"c{seed}-{i}" for i in range(n)]
            lbs = [Loopback(InProcessReplica(tiny_model, _ecfg(),
                                             replica_id=f"S{seed}{j}"))
                   for j in range(3)]
            for lb in lbs:
                # peer data plane on: KV/prefix ships go worker↔worker
                # by ticket, degrading to relay/recompute under fire
                lb.handle.peer_endpoint = lb.inner.start_peer()
            # disaggregated roles (third replica serves both) so the
            # storm exercises prefill->decode ships under fire too
            router = FleetRouter(
                [lb.handle for lb in lbs],
                FleetConfig(roles={f"S{seed}0": "prefill",
                                   f"S{seed}1": "decode"},
                            prefix_ship_threshold=1))
            # fleet prefix layer under fire: warm one 2-block shared
            # header pre-faults, then advertise every replica's digest
            # via manual registry beats (loopback handles are
            # self_heartbeat — in tests nothing beats for them), so
            # the storm dispatches on adverts that go stale the moment
            # churn evicts the blocks. Threshold 1: the first affinity
            # match already makes the header ship-eligible.
            shared = [int(t) for t in sched.integers(
                1, tiny_model.config.vocab_size, size=8)]
            router.add_request(f"c{seed}-warm", shared + [7, 8, 9],
                               sampling=_sp(False))
            _drain_router(router)
            for lb in lbs:
                router.registry.heartbeat(
                    lb.handle.replica_id,
                    meta={"prefix": lb.handle.prefix_digest()})
            for i in range(4):
                rid = f"c{seed}-h{i}"
                ids.append(rid)
                tail = [int(t) for t in sched.integers(
                    1, tiny_model.config.vocab_size,
                    size=3 + int(sched.integers(0, 3)))]
                prompts.append(shared + tail)
                router.add_request(rid, shared + tail,
                                   sampling=_sp(i % 2 == 0))
            for i, (rid, p) in enumerate(zip(ids[:n], prompts[:n])):
                router.add_request(rid, p, sampling=_sp(i % 2 == 1))
            spec = ";".join([
                f"fleet.worker_kill:flag:S{seed}0"
                f"@{sched.integers(2, 5)}*1",
                f"fleet.worker_kill:flag:S{seed}1"
                f"@{sched.integers(5, 8)}*1",
                f"fleet.rpc_drop:flag@{sched.integers(3, 30)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.rpc_delay:sleep:0.01@{sched.integers(1, 20)}"
                f"*{sched.integers(1, 4)}",
                # KV-ship chaos: dropped/corrupt ships must degrade to
                # recompute without duplicating or stranding a request
                f"fleet.kv_ship_drop:flag@{sched.integers(1, 5)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.kv_ship_corrupt:flag@{sched.integers(1, 5)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.kv_ship_delay:flag:0.005@{sched.integers(1, 8)}"
                f"*{sched.integers(1, 3)}",
                # proactive prefix ships under the same fire: dropped
                # or corrupted ships must leave the destination merely
                # cold, never corrupt
                f"fleet.prefix_ship_drop:flag@{sched.integers(0, 2)}"
                f"*{sched.integers(1, 2)}",
                f"fleet.prefix_ship_corrupt:flag@{sched.integers(0, 2)}"
                f"*{sched.integers(1, 2)}",
                # peer-rung chaos: failed pushes must degrade one rung
                # (relay, then recompute) with every ticket accounted
                f"fleet.peer_connect_fail:flag@{sched.integers(0, 3)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.peer_send_drop:flag@{sched.integers(0, 3)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.peer_frame_corrupt:flag@{sched.integers(0, 3)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.peer_stall:sleep:0.05@{sched.integers(0, 3)}"
                f"*{sched.integers(1, 2)}",
            ])
            faults.install(spec)
            outs = _drain_router(router, max_steps=400)
            faults.clear()
            if not router.dispatchable() and router.has_unfinished():
                # everything died with work queued: the supervisor's
                # job is a fresh replica; here the test plays it
                fresh = Loopback(InProcessReplica(
                    tiny_model, _ecfg(), replica_id=f"S{seed}9"))
                router.attach_replica(fresh.handle)
                lbs.append(fresh)
                outs += _drain_router(router, max_steps=400)
            final = {o.request_id: o for o in outs if o.finished}
            assert set(final) == set(ids)            # no strands
            assert all(final[r].finish_reason in FINISH_REASONS
                       for r in ids)
            counts = {}
            for o in outs:
                if o.token is not None:
                    counts[o.request_id] = counts.get(o.request_id,
                                                      0) + 1
            for r in ids:                            # no duplicates
                assert counts.get(r, 0) == len(final[r].generated), r
            for lb in lbs:                           # pools return full
                if lb.handle.alive:
                    bm = lb.inner.engine.block_manager
                    assert bm.num_free_blocks == bm.num_blocks
                    assert bm.num_free_host_blocks == bm.num_host_blocks
                    # no survivor holds uncommitted staged peer payloads
                    lis = lb.inner.peer_listener
                    if lis is not None:
                        lis.gc()
                        assert lis.pending_count == 0
            # ticket accounting survives the storm: every issued ticket
            # ended in exactly one counted outcome
            assert router.num_tickets_issued == \
                sum(router.ticket_outcomes.values())
            # the prefix layer was actually exercised: at least one
            # proactive ship was attempted (landed or failed cleanly)
            assert (router.num_prefix_ships
                    + router.num_prefix_ship_failures) >= 1


# ---------------------------------------------------------------------------
# disaggregated serving: prefill/decode roles + KV-ship (ISSUE 13)
# ---------------------------------------------------------------------------
def _disagg_pair(model, seed_prefix="P"):
    lb_p = Loopback(InProcessReplica(model, _ecfg(),
                                     replica_id=f"{seed_prefix}pre"))
    lb_d = Loopback(InProcessReplica(model, _ecfg(),
                                     replica_id=f"{seed_prefix}dec"))
    router = FleetRouter(
        [lb_p.handle, lb_d.handle],
        FleetConfig(roles={f"{seed_prefix}pre": "prefill",
                           f"{seed_prefix}dec": "decode"}))
    return lb_p, lb_d, router


def _token_counts(outs):
    counts = {}
    for o in outs:
        if o.token is not None:
            counts[o.request_id] = counts.get(o.request_id, 0) + 1
    return counts


class TestDisaggKVShip:
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_disagg_parity_over_transport(self, tiny_model, sampled):
        # THE tentpole pin: every request prefills on the prefill-role
        # replica, its committed KV ships over the wire (binary frame),
        # and the decode-role replica continues it mid-context — token
        # streams bit-identical to an uninterrupted single engine,
        # with ZERO prompt tokens recomputed.
        sp = _sp(sampled)
        n = 5
        prompts = _prompts(tiny_model, n)
        ids = [f"g{i}" for i in range(n)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb_p, lb_d, router = _disagg_pair(tiny_model, "A")
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert _token_counts(outs) == {r: len(ref[r]) for r in ids}
        assert router.num_kv_ship_requests == n
        assert router.num_kv_ship_bytes > 0
        assert router.num_kv_ship_blocks > 0
        assert router.num_tokens_recomputed == 0
        assert router.num_recompute_fallbacks == 0
        # ships are planned transfers, not failure hand-offs
        assert router.num_handoffs == 0
        assert lb_d.inner.engine.num_continuation_admits == n
        snap = router.snapshot()
        assert snap["fleet_kv_ship_requests"] == n
        assert isinstance(snap["fleet_kv_ship_ms_avg"], float)

    def test_drain_hand_off_ships_blocks_zero_recompute(self,
                                                        tiny_model):
        # SIGTERM-drain upgrade: the drain reply piggybacks the parked
        # KV, the peer imports it, and the hand-off recomputes ZERO
        # prompt tokens (counter-asserted) — still bit-identical.
        sp = _sp(True)
        prompts = _prompts(tiny_model, 4)
        ids = [f"dr{i}" for i in range(4)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb0 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                        replica_id="B0"))
        lb1 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                        replica_id="B1"))
        router = FleetRouter([lb0.handle, lb1.handle])
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        for _ in range(4):
            router.step()   # everyone well into decode
        router.retire_replica(lb0.handle)
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert router.num_handoffs >= 1
        assert router.num_kv_ship_requests >= 1
        assert router.num_tokens_recomputed == 0
        assert router.num_recompute_fallbacks == 0

    @pytest.mark.parametrize("fault", ["drop", "corrupt"],
                             ids=["dropped", "corrupt"])
    def test_kv_ship_fault_falls_back_to_recompute(self, tiny_model,
                                                   fault):
        # a dropped ship never reaches the peer; a corrupt one fails
        # the import-side CRC. Both degrade to resume-by-recompute on
        # the decode side — bit-identical, never duplicated or lost.
        sp = _sp(True)
        n = 4
        prompts = _prompts(tiny_model, n)
        ids = [f"f{fault[0]}{i}" for i in range(n)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb_p, lb_d, router = _disagg_pair(tiny_model, fault[0].upper())
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        faults.install(f"fleet.kv_ship_{fault}:flag*{n}")
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert _token_counts(outs) == {r: len(ref[r]) for r in ids}
        assert router.num_recompute_fallbacks == n
        assert router.num_kv_ship_requests == 0
        assert router.num_tokens_recomputed > 0
        assert lb_d.inner.engine.num_continuation_admits == 0

    def test_decode_replica_sigkill_recompute_fallback(self,
                                                       tiny_model):
        # crash hand-off: the decode replica dies mid-decode with no
        # farewell; its requests recover from router-side bookkeeping
        # by recompute on the surviving decode replica — bit-identical.
        sp = _sp(True)
        n = 4
        prompts = _prompts(tiny_model, n)
        ids = [f"x{i}" for i in range(n)]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb_p = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                         replica_id="Xpre"))
        lb_d0 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                          replica_id="Xdec0"))
        lb_d1 = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                          replica_id="Xdec1"))
        router = FleetRouter(
            [lb_p.handle, lb_d0.handle, lb_d1.handle],
            FleetConfig(roles={"Xpre": "prefill", "Xdec0": "decode",
                               "Xdec1": "decode"}))
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        faults.install("fleet.worker_kill:flag:Xdec0@4*1")
        outs = _drain_router(router, max_steps=400)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert _token_counts(outs) == {r: len(ref[r]) for r in ids}
        assert not lb_d0.handle.alive
        assert router.num_replicas_dead == 1
        assert router.num_kv_ship_requests >= 1
        # the stranded requests resumed by recompute somewhere
        assert router.num_tokens_recomputed > 0

    def test_no_decode_peer_keeps_decoding_on_prefill_replica(
            self, tiny_model):
        # availability beats purity: a prefill-only fleet never ships
        # (no peer) and still serves correctly
        sp = _sp(False)
        prompts = _prompts(tiny_model, 2)
        ids = ["np0", "np1"]
        ref = _reference(tiny_model, prompts, sp, ids)
        lb = Loopback(InProcessReplica(tiny_model, _ecfg(),
                                       replica_id="solo"))
        router = FleetRouter([lb.handle],
                             FleetConfig(roles={"solo": "prefill"}))
        for rid, p in zip(ids, prompts):
            router.add_request(rid, p, sampling=sp)
        outs = _drain_router(router)
        final = {o.request_id: o for o in outs if o.finished}
        assert {r: list(final[r].generated) for r in ids} == ref
        assert router.num_kv_ship_requests == 0
        assert router.num_recompute_fallbacks == 0

    def test_role_relearned_from_registry_heartbeat(self):
        # restart story: a rebuilt router attaches role-less handles;
        # the worker's self-heartbeat meta carries the role and the
        # next health sweep re-learns it
        reg = ReplicaRegistry(MemStore(), ttl_s=30.0)
        h = _StubReplica()
        h.replica_id = "w0-g1"
        h.self_heartbeat = True
        h.role = None
        router = FleetRouter([h], registry=reg)
        reg.heartbeat("w0-g1", meta={"pid": 1234, "role": "decode"})
        router.step()
        assert h.role == "decode"
        # sticky: later beats without meta must not erase it
        reg.heartbeat("w0-g1", meta={"pid": 1234})
        router.step()
        assert h.role == "decode"

    def test_export_import_content_identical(self, tiny_model):
        # the shipped bytes land bit-for-bit: gather the source blocks
        # and the imported blocks off both engines and compare
        eng_a = InProcessReplica(tiny_model, _ecfg(),
                                 replica_id="ca").engine
        eng_b = InProcessReplica(tiny_model, _ecfg(),
                                 replica_id="cb").engine
        sp = SamplingParams(max_new_tokens=4)
        prompt = _prompts(tiny_model, 1)[0] * 3   # multi-block prompt
        eng_a.add_request("src", prompt, sampling=sp)
        eng_a.step()   # prefill commits + first token
        req = eng_a.get_request("src")
        assert req.num_cached > 0
        meta, payload = eng_a.export_kv("src")
        src_table = eng_a.block_manager.export_blocks(
            "src", meta["tokens_covered"])
        k_src, v_src = eng_a.kv_frames.fetch(src_table)
        eng_b.import_kv("dst", list(req.tokens), sampling=sp,
                        meta=meta, payload=payload)
        dst_table = eng_b.block_manager.export_blocks(
            "dst", meta["tokens_covered"])
        k_dst, v_dst = eng_b.kv_frames.fetch(dst_table)
        np.testing.assert_array_equal(k_src, k_dst)
        np.testing.assert_array_equal(v_src, v_dst)
        bm = eng_b.block_manager
        for b in dst_table:
            assert bm.ref_count(b) == 1
        eng_b.abort_request("dst")
        eng_b.release_request("dst")
        eng_a.abort_request("src")
        eng_a.release_request("src")
        for eng in (eng_a, eng_b):
            bm = eng.block_manager
            bm.check_invariants()
            assert bm.num_free_blocks == bm.num_blocks


# ---------------------------------------------------------------------------
# hand-off budget + dead-handle bookkeeping regressions (model-free)
# ---------------------------------------------------------------------------
class FakeReplica(ReplicaHandle):
    """Same shape as test_fleet.FakeReplica, trimmed to what's used."""

    def __init__(self, replica_id, ttft=None, capacity=8):
        self.replica_id = replica_id
        self.alive = True
        self.retiring = False
        self.ttft = ttft
        self.capacity = capacity
        self.reqs = {}
        self.dispatch_log = []
        self._draining = False

    def admission_verdict(self, prompt_tokens):
        if not self.alive:
            return "replica is dead"
        if self._draining:
            return "replica is draining"
        if len(self.reqs) >= self.capacity:
            return "queue full"
        return None

    def estimated_ttft_ms(self, prompt_tokens):
        return self.ttft

    def load(self):
        return ReplicaLoad(num_running=len(self.reqs),
                           kv_utilization=min(1.0, len(self.reqs)
                                              / max(self.capacity, 1)))

    @property
    def is_draining(self):
        return self._draining

    @property
    def drained(self):
        return self._draining and not self.reqs

    def has_unfinished(self):
        return self.alive and bool(self.reqs)

    def add_request(self, request_id, prompt_ids, sampling, *,
                    rng_state=None):
        self.reqs[request_id] = [sampling, []]
        self.dispatch_log.append(request_id)

    def abort_request(self, request_id):
        return self.reqs.pop(request_id, None) is not None

    def release_request(self, request_id):
        self.reqs.pop(request_id, None)

    def rng_state(self, request_id):
        return {"fake_state_for": request_id}

    def step(self):
        if not self.alive:
            return []
        outs = []
        for rid in list(self.reqs):
            sp, gen = self.reqs[rid]
            gen.append(1000 + len(gen))
            done = len(gen) >= sp.max_new_tokens
            outs.append(RequestOutput(
                request_id=rid, token=gen[-1], finished=done,
                generated=list(gen),
                finish_reason="length" if done else None))
            if done:
                del self.reqs[rid]
        return outs

    def start_drain(self, reason="manual"):
        self._draining = True
        outs = []
        for rid in list(self.reqs):
            sp, gen = self.reqs.pop(rid)
            outs.append(RequestOutput(
                request_id=rid, token=None, finished=True,
                generated=list(gen), finish_reason="aborted:drain"))
        return outs


class TestHandoffBudget:
    def test_process_death_consumes_budget_exactly_once(self):
        # the handle dies outside the router's sight; however many
        # health-sweep passes observe the corpse, each stranded request
        # pays ONE hand-off slot for the one death
        ra = FakeReplica("ra", ttft=1.0)
        rb = FakeReplica("rb", ttft=9.0)
        router = FleetRouter([ra, rb])
        rids = [router.add_request([1], SamplingParams(max_new_tokens=6))
                for _ in range(2)]
        router.step()
        assert ra.dispatch_log == rids
        ra.alive = False                     # process gone
        outs = []
        router._health_sweep(outs)           # discovery pass
        for _ in range(4):                   # sweep spam: same corpse
            router._health_sweep(outs)
        assert router.num_handoffs == 2      # one slot per request
        assert all(router.get_request(r).handoffs == 1 for r in rids)
        assert router.num_replicas_dead == 1
        final = {o.request_id: o for o in _drain_router(router)
                 if o.finished}
        assert all(final[r].finish_reason == "length" for r in rids)
        assert all(router.get_request(r).handoffs == 1 for r in rids)

    def test_repeated_kill_replica_is_idempotent(self):
        ra = FakeReplica("ra", ttft=1.0)
        rb = FakeReplica("rb", ttft=9.0)
        router = FleetRouter([ra, rb])
        rid = router.add_request([1], SamplingParams(max_new_tokens=4))
        router.step()
        outs = []
        router.kill_replica("ra", outputs=outs)
        router.kill_replica("ra", outputs=outs)
        router.kill_replica("ra", outputs=outs)
        assert router.num_replicas_dead == 1
        assert router.num_handoffs == 1
        assert router.get_request(rid).handoffs == 1

    def test_handoff_exhausted_counter_pinned(self):
        ra = FakeReplica("ra", ttft=1.0)
        rb = FakeReplica("rb", ttft=9.0)
        router = FleetRouter([ra, rb], FleetConfig(max_handoffs=0))
        rid = router.add_request([1], SamplingParams(max_new_tokens=4))
        router.step()
        outs = []
        router.kill_replica("ra", outputs=outs)
        assert router.num_handoff_exhausted == 1
        assert [o.finish_reason for o in outs] == ["aborted:error"]
        router.kill_replica("ra", outputs=outs)    # corpse re-kill
        assert router.num_handoff_exhausted == 1   # not re-counted
        assert router.snapshot()["fleet_handoff_exhausted"] == 1
        assert router.get_request(rid).finish_reason == "aborted:error"

    def test_handoff_exhausted_counts_drain_path_too(self):
        class DrainOnStep(FakeReplica):
            def step(self):
                if self.reqs and not self._draining:
                    return self.start_drain("unstable")
                return super().step()

        router = FleetRouter(
            [DrainOnStep("ra"), DrainOnStep("rb"), DrainOnStep("rc")],
            FleetConfig(max_handoffs=1))
        router.add_request([1], SamplingParams(max_new_tokens=4))
        outs = _drain_router(router)
        assert [o.finish_reason for o in outs
                if o.finished] == ["aborted:drain"]
        assert router.num_handoffs == 1
        assert router.num_handoff_exhausted == 1

    def test_abort_on_dead_replica_unassigns(self):
        # pre-fix, the dead handle kept the aborted request in
        # _assigned and every health sweep "recovered" the corpse again
        ra = FakeReplica("ra", ttft=1.0)
        rb = FakeReplica("rb", ttft=9.0)
        router = FleetRouter([ra, rb])
        rid = router.add_request([1], SamplingParams(max_new_tokens=9))
        router.step()
        ra.alive = False
        assert router.abort_request(rid) is True
        assert not router._assigned["ra"]
        outs = []
        for _ in range(3):
            router._health_sweep(outs)
        assert router.num_replicas_dead == 0   # nothing left to recover
        assert router.num_handoffs == 0


# ---------------------------------------------------------------------------
# registry: skew-immune monotonic liveness
# ---------------------------------------------------------------------------
class TestRegistryMonotonic:
    def test_wall_clock_skew_cannot_fake_death(self):
        # a writer whose wall clock is 999s behind still reads as alive:
        # liveness keys on the record CHANGING, not on its ts field
        store = MemStore()
        writer = ReplicaRegistry(store, ttl_s=2.0)
        reader = ReplicaRegistry(store, ttl_s=2.0)
        writer.heartbeat("w", now=time.time() - 999.0)   # skewed clock
        assert reader.is_alive("w")
        writer.heartbeat("w", now=time.time() - 999.0)
        assert set(reader.alive()) == {"w"}

    def test_silence_past_ttl_is_death_on_reader_clock(self):
        store = MemStore()
        writer = ReplicaRegistry(store, ttl_s=2.0)
        reader = ReplicaRegistry(store, ttl_s=2.0)
        writer.heartbeat("w")
        t0 = time.monotonic()
        reader._mono = lambda: t0
        assert reader.is_alive("w")                 # observed at t0
        reader._mono = lambda: t0 + 1.5
        assert reader.is_alive("w")                 # inside ttl
        reader._mono = lambda: t0 + 2.5
        assert reader.is_alive("w") is False        # silent past ttl
        writer.heartbeat("w")                       # resumes
        assert reader.is_alive("w")

    def test_writer_restart_reads_as_fresh(self):
        # a restarted worker's counter restarts too; the nonce makes
        # the record read as changed, never as a stale continuation
        store = MemStore()
        w1 = ReplicaRegistry(store, ttl_s=2.0)
        reader = ReplicaRegistry(store, ttl_s=2.0)
        for _ in range(3):
            w1.heartbeat("w")
        t0 = time.monotonic()
        reader._mono = lambda: t0
        assert reader.is_alive("w")
        reader._mono = lambda: t0 + 5.0             # w1 long silent
        assert reader.is_alive("w") is False
        w2 = ReplicaRegistry(store, ttl_s=2.0)      # new process
        w2.heartbeat("w")
        assert reader.is_alive("w")

    def test_legacy_record_without_seq_falls_back_to_ts(self):
        import json

        store = MemStore()
        reader = ReplicaRegistry(store, ttl_s=5.0)
        store.set("serving_fleet/hb/old",
                  json.dumps({"ts": time.time()}))
        assert reader.is_alive("old")
        store.set("serving_fleet/hb/old",
                  json.dumps({"ts": time.time() - 100.0}))
        assert reader.is_alive("old") is False

    def test_explicit_now_keeps_simulated_clock_contract(self):
        reg = ReplicaRegistry(MemStore(), ttl_s=5.0)
        reg.heartbeat("a", now=100.0)
        assert reg.is_alive("a", now=104.0)
        assert reg.is_alive("a", now=106.0) is False

    def test_worker_kill_fault_noop_without_hard_kill(self):
        ra = FakeReplica("ra", ttft=1.0)
        rb = FakeReplica("rb", ttft=9.0)
        router = FleetRouter([ra, rb])
        rid = router.add_request([1], SamplingParams(max_new_tokens=3))
        faults.install("fleet.worker_kill:flag:ra*1")
        outs = _drain_router(router)
        assert ra.alive                       # no transport, no SIGKILL
        final = {o.request_id: o.finish_reason for o in outs
                 if o.finished}
        assert final == {rid: "length"}


# ---------------------------------------------------------------------------
# replicated control plane under fire (ISSUE 16): two routers, the
# lease fault points joined to the transport storm
# ---------------------------------------------------------------------------
class TestReplicatedStorm:
    def test_replicated_chaos_storm_exact_accounting(self, tiny_model):
        """Two loopback routers over one shared store, the full fault
        menu at once: the owning router SIGKILLed mid-storm, a lease
        renewal dropped, a live lease stolen, a worker killed, RPC
        drops and delays. Invariants, not outcomes: every request in
        exactly one terminal bucket, every token delivered exactly
        once, no orphaned lease, every issued ticket in exactly one
        outcome, peer listeners empty, surviving pools full."""
        from paddle_tpu.serving.fleet import LeaseStore

        for seed in (0, 1):
            sched = np.random.default_rng(100 + seed)
            n = 8
            prompts = _prompts(tiny_model, n, seed=40 + seed)
            ids = [f"rs{seed}-{i}" for i in range(n)]
            lbs = [Loopback(InProcessReplica(
                       tiny_model, _ecfg(), replica_id=f"RS{seed}{j}"))
                   for j in range(3)]
            for lb in lbs:
                lb.handle.peer_endpoint = lb.inner.start_peer()
            store = MemStore()
            cfg = FleetConfig(heartbeat_interval_s=0.0,
                              router_ttl_s=0.3, lease_ttl_s=0.6)
            routers = []
            for name in ("A", "B"):
                reg = ReplicaRegistry(store, ttl_s=30.0)
                routers.append(FleetRouter(
                    [lb.handle for lb in lbs], cfg, reg,
                    lease_store=LeaseStore(store, ttl_s=0.6),
                    router_id=f"{name}{seed}"))
            ra, rb = routers
            ra.step(); rb.step()  # discover each other
            for i, (rid, p) in enumerate(zip(ids, prompts)):
                (ra if i % 2 == 0 else rb).add_request(
                    rid, p, sampling=_sp(i % 2 == 1))
            outs = []

            def joint(steps):
                for _ in range(steps):
                    for r in routers:
                        outs.extend(r.step())

            joint(3)  # every request dispatched AND leased
            spec = ";".join([
                # the router holding half the traffic dies mid-decode
                f"fleet.router_kill:flag:A{seed}"
                f"@{sched.integers(1, 3)}*1",
                # one renewal write dropped: owner must self-fence and
                # the request recovers through the expired bucket
                f"fleet.lease_expire:flag:{ids[2]}*1",
                # a live lease force-adopted out from under its owner
                f"fleet.lease_steal:flag:{ids[5]}*1",
                # plus the PR-12/14 transport storm underneath
                f"fleet.worker_kill:flag:RS{seed}0"
                f"@{sched.integers(2, 6)}*1",
                f"fleet.rpc_drop:flag@{sched.integers(3, 30)}"
                f"*{sched.integers(1, 3)}",
                f"fleet.rpc_delay:sleep:0.01@{sched.integers(1, 20)}"
                f"*{sched.integers(1, 4)}",
            ])
            faults.install(spec)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                joint(1)
                live = [r for r in routers if not r.router_dead]
                # quiesce = live routers idle AND no lease open: the
                # dead router's requests stay leased until a peer's
                # sweep adopts and finishes them
                if (not any(r.has_unfinished() for r in live)
                        and routers[0].lease_store.active() == 0):
                    break
                time.sleep(0.005)
            faults.clear()
            live = [r for r in routers if not r.router_dead]
            assert live and not any(r.has_unfinished() for r in live)

            # every request reached EXACTLY ONE terminal, fleet-wide
            final = {}
            for o in outs:
                if o.finished:
                    assert o.request_id not in final, \
                        f"{o.request_id} got two terminals"
                    final[o.request_id] = o
            assert set(final) == set(ids)  # no strands
            assert all(final[r].finish_reason in FINISH_REASONS
                       for r in ids)
            # every token delivered exactly once (failover replays
            # nothing, fencing loses nothing)
            counts = {}
            for o in outs:
                if o.token is not None:
                    counts[o.request_id] = counts.get(o.request_id,
                                                      0) + 1
            for r in ids:
                assert counts.get(r, 0) == len(final[r].generated), r
            # the failover actually happened and was counted once
            assert ra.router_dead
            assert sum(r.num_router_failovers for r in routers) == 1
            # lease accounting is exact: every incarnation in exactly
            # one bucket, nothing orphaned at quiesce
            acq = sum(r.lease_store.num_acquired for r in routers)
            closed = sum(r.lease_store.num_completed
                         + r.lease_store.num_adopted
                         + r.lease_store.num_expired for r in routers)
            assert acq == closed
            assert routers[0].lease_store.active() == 0
            # the injected lease faults really fired
            assert sum(r.lease_store.num_renew_dropped
                       for r in routers) >= 1
            # per-router ticket accounting partitions
            for r in routers:
                assert r.num_tickets_issued == \
                    sum(r.ticket_outcomes.values())
            # surviving engines: pools back to full, listeners empty
            for lb in lbs:
                if lb.handle.alive:
                    bm = lb.inner.engine.block_manager
                    assert bm.num_free_blocks == bm.num_blocks
                    lis = lb.inner.peer_listener
                    if lis is not None:
                        lis.gc()
                        assert lis.pending_count == 0
