"""CI smoke for TP-sharded serving (scripts/ci.sh --tp).

Runs on a FORCED 4-device host mesh (tier-1 stays single-device) and
pins the ISSUE-17 acceptance observables:

* TP=2 ragged serving is token-identical to the TP=1 engine on a
  mixed greedy+sampled workload — through a forced-OOM preemption and
  prefix-cache hits — with zero attention-path padding;
* the same workload under ``swap_mode='host'``: the OOM victim's KV
  spills to host RAM as layout-sharded frames (``Layout.shard_frames``)
  and restores on readmit bit-exactly at both degrees;
* a KV ship from a TP=1 exporter into a TP=2 importer lands through
  ``redistribute`` (reshard counter + redistribute stats asserted)
  with ZERO prompt tokens recomputed (exactly the one mandatory
  position is computed on the importer);
* the same cross-degree ship at FLEET level: draining a TP=1 replica
  hands its in-flight requests to a TP=2 peer with token parity and
  ``fleet/tokens_recomputed == 0``, and an injected scatter fault
  falls back down the PR-14 ladder to recompute — never loss or
  duplication;
* ``CheckpointManager.restore(target_layout=...)`` restores one
  checkpoint onto the TP=2 layouts with logits bit-identical to the
  unsharded restore.
"""
import os

# the mesh must exist before jax initialises — set both knobs before
# ANY jax-importing module loads
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import CheckpointManager
from paddle_tpu.distributed.redistribute import get_stats, reset_stats
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.fleet import FleetRouter, InProcessReplica
from paddle_tpu.testing import faults


def build_model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _ecfg(tp, **kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("drain_grace_s", 0.0)
    return EngineConfig(tp_degree=tp, **kw)


def make_workload(vocab):
    rng = np.random.default_rng(17)
    shared = list(map(int, rng.integers(0, vocab, size=16)))
    prompts = [
        shared + list(map(int, rng.integers(0, vocab, size=6))),
        list(map(int, rng.integers(0, vocab, size=5))),
        shared + list(map(int, rng.integers(0, vocab, size=3))),
        list(map(int, rng.integers(0, vocab, size=8))),
        shared + list(map(int, rng.integers(0, vocab, size=9))),
        list(map(int, rng.integers(0, vocab, size=4))),
    ]
    samplings = [
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=5, temperature=0.8, seed=11),
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=4, temperature=0.7, top_p=0.9,
                       seed=3),
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=5),
    ]
    return prompts, samplings


def serve(model, tp):
    """The mixed workload on one engine, with a forced-OOM preemption
    of request r0 mid-decode (same fault schedule both degrees)."""
    prompts, samplings = make_workload(model.config.vocab_size)
    eng = LLMEngine(model, _ecfg(tp))
    rids = [eng.add_request(f"r{i}", p, sampling=sp)
            for i, (p, sp) in enumerate(zip(prompts, samplings))]
    faults.install("serving.force_oom.r0:flag*1")
    try:
        while eng.has_unfinished():
            eng.step()
            eng.block_manager.check_invariants()
    finally:
        faults.clear()
    return eng, {r: list(eng.get_request(r).generated) for r in rids}


def parity_phase(model):
    e1, out1 = serve(model, tp=1)
    e2, out2 = serve(model, tp=2)
    assert out1 == out2, "TP=2 diverged from TP=1:\n%r\n%r" % (out1, out2)
    s1, s2 = e1.metrics.snapshot(), e2.metrics.snapshot()
    for s in (s1, s2):
        assert s["preemptions"] >= 1, s["preemptions"]
        assert s["serving_prefix_cache_hits"] >= 1, s
    assert e2.tp_degree == 2 and e2.kv_layout.size == 2
    print("TP_PARITY_OK reqs=%d preempt=%d prefix_hits=%d"
          % (len(out1), s2["preemptions"],
             s2["serving_prefix_cache_hits"]), flush=True)


def host_swap_phase(model):
    """Swap-based preemption at TP=2: the forced-OOM victim's KV
    blocks spill to HOST memory as layout-sharded frames
    (``Layout.shard_frames``) and restore on readmit — token parity
    against the TP=1 host-swap engine proves the per-shard frame
    round-trip reassembled bit-exactly."""
    outs, snaps = {}, {}
    for tp in (1, 2):
        prompts, samplings = make_workload(model.config.vocab_size)
        eng = LLMEngine(model, _ecfg(tp, swap_mode="host",
                                     num_blocks=16))
        rids = [eng.add_request(f"r{i}", p, sampling=sp)
                for i, (p, sp) in enumerate(zip(prompts, samplings))]
        faults.install("serving.force_oom.r0:flag*1")
        try:
            while eng.has_unfinished():
                eng.step()
                eng.block_manager.check_invariants()
        finally:
            faults.clear()
        outs[tp] = {r: list(eng.get_request(r).generated)
                    for r in rids}
        snaps[tp] = eng.metrics.snapshot()
    assert outs[1] == outs[2], \
        "TP=2 host-swap diverged from TP=1:\n%r\n%r" % (outs[1],
                                                        outs[2])
    for tp, s in snaps.items():
        assert s["serving_swapped_out"] >= 1 \
                and s["serving_swapped_in"] >= 1, (tp, s)
    print("TP_HOST_SWAP_OK swapped_out=%d swapped_in=%d"
          % (snaps[2]["serving_swapped_out"],
             snaps[2]["serving_swapped_in"]),
          flush=True)


def cross_degree_ship_phase(model):
    """TP=1 exporter -> TP=2 importer, direct engine seam."""
    rng = np.random.default_rng(23)
    prompt = list(map(int, rng.integers(0, model.config.vocab_size,
                                        size=32)))
    max_new = 6
    ref_eng = LLMEngine(model, _ecfg(1))
    ref = ref_eng.generate([prompt],
                           SamplingParams(max_new_tokens=max_new))[0]

    e1 = LLMEngine(model, _ecfg(1))
    e1.add_request("ship", prompt,
                   sampling=SamplingParams(max_new_tokens=max_new))
    for _ in range(2):
        e1.step()
    done = list(e1.get_request("ship").generated)
    meta, payload = e1.export_kv("ship")
    assert meta["layout"]["mesh_axes"] == [["tp", 1]]

    e2 = LLMEngine(model, _ecfg(2))
    reset_stats()
    full_prompt = prompt + done
    e2.import_kv("ship", full_prompt,
                 sampling=SamplingParams(max_new_tokens=max_new
                                         - len(done)),
                 meta=meta, payload=payload)
    while e2.has_unfinished():
        e2.step()
    got = done + list(e2.get_request("ship").generated)
    assert got == ref, "shipped continuation diverged:\n%r\n%r" % (got,
                                                                   ref)
    st = get_stats()
    assert e2.num_kv_reshards == 1
    assert st["num_redistributes"] >= 1 and st["bytes_total"] > 0, st
    # zero recompute: the importer computed exactly the ONE mandatory
    # uncovered position, nothing else
    covered = meta["tokens_covered"]
    computed = e2.metrics.snapshot()["num_prompt_tokens"]
    assert computed == len(full_prompt) - covered == 1, \
        (computed, len(full_prompt), covered)
    snap = e2.metrics.snapshot()
    assert snap["serving_kv_reshards"] == 1
    assert snap["serving_continuation_resumes"] >= 1
    print("TP_CROSS_SHIP_OK covered=%d computed=%d redistributes=%d "
          "bytes_total=%d" % (covered, computed, st["num_redistributes"],
                              st["bytes_total"]), flush=True)


def _drain_router(router, max_steps=600):
    steps = 0
    while router.has_unfinished():
        router.step()
        steps += 1
        assert steps < max_steps, "router failed to converge"
    return steps


def fleet_handoff_phase(model, inject_fault):
    """Drain a TP=1 replica mid-run: its in-flight requests ship to
    the TP=2 peer. Clean path = zero tokens recomputed; injected
    scatter fault = one rung down the ladder (recompute), same
    tokens either way."""
    prompts, samplings = make_workload(model.config.vocab_size)
    ref_eng = LLMEngine(model, _ecfg(1))
    rids_ref = [ref_eng.add_request(f"f{i}", p, sampling=sp)
                for i, (p, sp) in enumerate(zip(prompts, samplings))]
    while ref_eng.has_unfinished():
        ref_eng.step()
    ref = {r: list(ref_eng.get_request(r).generated) for r in rids_ref}

    r1 = InProcessReplica(model, _ecfg(1), replica_id="tp1")
    r2 = InProcessReplica(model, _ecfg(2), replica_id="tp2")
    router = FleetRouter([r1, r2])
    for i, (p, sp) in enumerate(zip(prompts, samplings)):
        router.add_request(f"f{i}", p, sp)
    for _ in range(3):                  # everything dispatches + decodes
        router.step()
    if inject_fault:
        faults.install("serving.kv_scatter:raise*1")
    try:
        router.retire_replica(r1, reason="tp-migration")
        _drain_router(router)
    finally:
        faults.clear()
    got = {f"f{i}": list(router.get_request(f"f{i}").generated)
           for i in range(len(prompts))}
    assert got == ref, "fleet hand-off diverged:\n%r\n%r" % (got, ref)
    snap = router.snapshot()
    assert snap["fleet_finish"] == {"length": len(prompts)}, snap
    if inject_fault:
        assert snap["fleet_recompute_fallbacks"] >= 1, snap
        print("TP_FLEET_FAULT_OK fallbacks=%d recomputed=%d"
              % (snap["fleet_recompute_fallbacks"],
                 snap["fleet_tokens_recomputed"]), flush=True)
    else:
        assert snap["fleet_kv_ship_requests"] >= 1, snap
        assert snap["fleet_tokens_recomputed"] == 0, snap
        assert snap["fleet_recompute_fallbacks"] == 0, snap
        assert r2.engine.num_kv_reshards >= 1
        print("TP_FLEET_SHIP_OK ships=%d reshards=%d recomputed=0"
              % (snap["fleet_kv_ship_requests"],
                 r2.engine.num_kv_reshards), flush=True)


def checkpoint_reshard_phase(model, tmp="/tmp/_tp_smoke_ckpt"):
    """One saved checkpoint, two restores: unsharded and onto the
    TP=2 serving layouts. The restore itself is bit-identical (every
    gathered parameter equals the unsharded restore exactly); the
    sharded FORWARD is float32-reduction-order away from the dense
    one (GSPMD partitions the matmuls), so logits are pinned to tight
    float32 tolerance and the served tokens must match exactly."""
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    mgr = CheckpointManager(tmp, dedupe_chunks=True)
    mgr.save(1, model.state_dict(), block=True)

    eng2 = LLMEngine(model, _ecfg(2))
    layouts = eng2.param_layouts()

    paddle.seed(123)
    plain = LlamaForCausalLM(LlamaConfig.tiny())
    plain.eval()
    mgr.restore(plain.state_dict(), step=1)

    paddle.seed(456)
    sharded = LlamaForCausalLM(LlamaConfig.tiny())
    sharded.eval()
    sd = sharded.state_dict()
    mgr.restore(sd, step=1,
                target_layout={k: layouts[k] for k in sd
                               if k in layouts},
                devices=eng2._tp_devices)

    # the restore moved ZERO bits: every resharded parameter gathers
    # back to exactly the unsharded restore's bytes
    psd = plain.state_dict()
    for k, v in sd.items():
        np.testing.assert_array_equal(
            np.asarray(v._data), np.asarray(psd[k]._data), err_msg=k)

    rng = np.random.default_rng(5)
    ids = paddle.to_tensor(rng.integers(
        0, model.config.vocab_size, size=(2, 12)).astype(np.int32))
    ref = plain(ids).numpy()
    got = sharded(ids).numpy()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)

    # and the tokens the TP=2 engine serves from the resharded weights
    # match the unsharded-restore engine exactly
    prompt = list(map(int, rng.integers(
        0, model.config.vocab_size, size=20)))
    sp = SamplingParams(max_new_tokens=6)
    toks_plain = LLMEngine(plain, _ecfg(1)).generate([prompt], sp)[0]
    toks_shard = LLMEngine(sharded, _ecfg(2)).generate([prompt], sp)[0]
    assert toks_shard == toks_plain, (toks_shard, toks_plain)
    shutil.rmtree(tmp, ignore_errors=True)
    print("TP_CKPT_RESHARD_OK params_resharded=%d"
          % sum(1 for l in layouts.values()
                if any(p is not None for p in l.dim_placements)),
          flush=True)


def main():
    import jax

    assert len(jax.devices()) >= 4, jax.devices()
    model = build_model()
    parity_phase(model)
    host_swap_phase(model)
    cross_degree_ship_phase(model)
    fleet_handoff_phase(model, inject_fault=False)
    fleet_handoff_phase(model, inject_fault=True)
    checkpoint_reshard_phase(model)
    print("TP_SMOKE_OK", flush=True)


if __name__ == "__main__":
    main()
