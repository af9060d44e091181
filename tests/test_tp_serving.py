"""TP-sharded serving units that run on ONE XLA:CPU device (ISSUE 17).

Multi-device parity — TP=2 token-identical to TP=1, cross-degree KV
resharding, resharded checkpoint restore — needs a forced 8-device
host mesh and lives in ``scripts/tp_smoke.py`` (``scripts/ci.sh --tp``).
What CAN be pinned on a single device is pinned here: the engine's TP
surface at degree 1 (layouts, gauges, wire-format defaults), the
BlockManager's rank gate on shipped payloads, the transport's
at-the-door layout refusal, and the checkpoint manager's
content-addressed chunk dedupe + GC and ``target_layout`` restore.
"""
import os
import zlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import CheckpointManager
from paddle_tpu.distributed.redistribute import Layout
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.block_manager import BlockManager
from paddle_tpu.serving.fleet import PeerListener, peer_push, sign_ticket


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _ecfg(**kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("drain_grace_s", 0.0)
    return EngineConfig(**kw)


# ---------------------------------------------------------------------------
# engine TP surface at degree 1 (the CI-visible slice)
# ---------------------------------------------------------------------------
class TestEngineTPSurface:
    def test_tp1_layouts_and_gauges(self, tiny_model):
        eng = LLMEngine(tiny_model, _ecfg())
        assert eng.tp_degree == 1
        # the cache layout names the kv-head dim of (L, NB, BS, KH, D)
        assert eng.kv_layout.ndim == 5
        assert eng.kv_layout.size == 1
        lays = eng.param_layouts()
        assert set(lays) == set(eng._pnames)
        # degree 1 = fully replicated: nothing splits
        assert all(all(p is None for p in l.dim_placements)
                   for l in lays.values())
        snap = eng.metrics.snapshot()
        assert snap["serving_kv_reshards"] == 0
        assert snap["serving_continuation_resumes"] == 0

    def test_param_layout_megatron_pairing(self):
        from paddle_tpu.serving.engine import _tp_param_layout
        # column-parallel: output features split (dim 1 of weight)
        q = _tp_param_layout("layers.0.self_attn.q_proj.weight", 2, 2)
        assert q.dim_placements == (None, "tp")
        qb = _tp_param_layout("layers.0.self_attn.q_proj.bias", 1, 2)
        assert qb.dim_placements == ("tp",)
        # row-parallel: input features split (dim 0 of weight)
        o = _tp_param_layout("layers.0.self_attn.o_proj.weight", 2, 2)
        assert o.dim_placements == ("tp", None)
        # embeddings / norms / lm_head stay replicated
        e = _tp_param_layout("embed_tokens.weight", 2, 2)
        assert e.dim_placements == (None, None)

    def test_wire_layout_default_and_rejection(self, tiny_model):
        eng = LLMEngine(tiny_model, _ecfg())
        shape = (2, 3, 4, 2, 8)
        # absent stanza = the pre-TP flat wire format: one replicated
        # frame — old exporters keep working against a TP importer
        lay = eng.kv_frames.wire_layout({}, shape)
        assert lay.size == 1 and lay.ndim == 5
        with pytest.raises(ValueError):
            eng.kv_frames.wire_layout({"layout": {"bogus": True}}, shape)
        # a layout that cannot tile the payload geometry is refused
        bad = Layout.tp_sharded(5, 3, 4).to_meta()
        with pytest.raises(ValueError):
            eng.kv_frames.wire_layout({"layout": bad}, (2, 3, 4, 2, 8)[:4])

    def test_tp_degree_must_divide_heads(self, tiny_model):
        with pytest.raises(ValueError, match="divide"):
            LLMEngine(tiny_model, _ecfg(tp_degree=3))


# ---------------------------------------------------------------------------
# BlockManager: shipped-payload rank gate
# ---------------------------------------------------------------------------
class TestBlockManagerLayoutGate:
    def test_rank_mismatch_refused_before_allocation(self):
        bm = BlockManager(8, 4, kv_layout=Layout.tp_sharded(5, 3, 1))
        with pytest.raises(ValueError, match="rank"):
            bm.import_blocks("r1", 8,
                             src_layout=Layout.tp_sharded(4, 2, 2))
        assert bm.num_free_blocks == 8      # nothing was claimed
        # matching rank lands regardless of degree (degree is the
        # engine's reshard problem, not the allocator's)
        blocks = bm.import_blocks("r1", 8,
                                  src_layout=Layout.tp_sharded(5, 3, 2))
        assert len(blocks) == 2

    def test_layoutless_manager_accepts_any(self):
        bm = BlockManager(8, 4)             # pre-TP construction
        blocks = bm.import_blocks("r1", 4,
                                  src_layout=Layout.tp_sharded(4, 2, 2))
        assert len(blocks) == 1


# ---------------------------------------------------------------------------
# transport: malformed layout stanzas refused at the door
# ---------------------------------------------------------------------------
class TestTransportLayoutGate:
    def _ticket(self, lis, tid="t1"):
        t = {"ticket_id": tid, "src": "a", "dst": "b", "kind": "kv",
             "request_id": "r0", "deadline_ms": 30_000}
        t["sig"] = sign_ticket(t, lis._secret)
        return t

    def _meta(self, payload, **extra):
        m = {"crc32": zlib.crc32(payload) & 0xFFFFFFFF}
        m.update(extra)
        return m

    def test_bad_layout_stanza_refused(self):
        lis = PeerListener()
        try:
            payload = b"x" * 64
            receipt = peer_push(
                lis.endpoint, self._ticket(lis),
                self._meta(payload, layout={"bogus": 1}), payload)
            assert receipt["ok"] is False
            assert "layout" in receipt["error"]
            assert lis.take("t1") is None
            assert lis.stats()["refused"] == 1
        finally:
            lis.close()

    def test_unframeable_payload_refused(self):
        # 2 shards need the K and V byte streams to split into 2x2
        # frames; 63 bytes cannot
        lis = PeerListener()
        try:
            payload = b"x" * 63
            lay = Layout.tp_sharded(5, 3, 2).to_meta()
            receipt = peer_push(
                lis.endpoint, self._ticket(lis),
                self._meta(payload, layout=lay), payload)
            assert receipt["ok"] is False
            assert "layout" in receipt["error"]
        finally:
            lis.close()

    def test_well_formed_layout_admitted(self):
        lis = PeerListener()
        try:
            payload = b"x" * 64
            lay = Layout.tp_sharded(5, 3, 2).to_meta()
            receipt = peer_push(
                lis.endpoint, self._ticket(lis),
                self._meta(payload, layout=lay), payload)
            assert receipt["ok"] is True
            ticket, meta, got = lis.take("t1")
            assert got == payload
            assert meta["layout"] == lay
        finally:
            lis.close()


# ---------------------------------------------------------------------------
# checkpoint: content-addressed chunk dedupe + GC, target_layout restore
# ---------------------------------------------------------------------------
def _state(step):
    # "frozen" never changes across steps (the dedupe win);
    # "hot" changes every step (must never dedupe)
    return {"frozen": paddle.full([8, 8], 3.25),
            "hot": paddle.full([4], float(step))}


class TestCheckpointCAS:
    def test_dedupe_hardlinks_identical_chunks(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last_n=5,
                                dedupe_chunks=True)
        for s in (1, 2, 3):
            mgr.save(s, _state(s), block=True)
        # steps 2 and 3 re-linked the frozen chunk instead of
        # rewriting it
        assert mgr.last_cas_hits >= 1
        cas = tmp_path / "chunk_cas"
        assert cas.is_dir()
        nlinks = sorted(os.stat(cas / f).st_nlink
                        for f in os.listdir(cas))
        # the frozen chunk: cas copy + one link per kept step
        assert nlinks[-1] == 4
        st = _state(0)
        st["hot"] = paddle.zeros([4])
        assert mgr.restore_or_initialize(st) == 3
        np.testing.assert_array_equal(st["frozen"].numpy(),
                                      np.full((8, 8), 3.25, np.float32))
        np.testing.assert_array_equal(st["hot"].numpy(),
                                      np.full(4, 3.0, np.float32))

    def test_gc_prunes_unreferenced_chunks(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1,
                                dedupe_chunks=True)
        mgr.save(1, _state(1), block=True)
        mgr.save(2, _state(2), block=True)
        assert mgr.all_steps() == [2]
        cas = tmp_path / "chunk_cas"
        # step 1's hot chunk lost its last step reference and was
        # pruned; the frozen chunk and step 2's hot chunk survive
        for f in os.listdir(cas):
            assert os.stat(cas / f).st_nlink >= 2, f
        st = _state(0)
        assert mgr.restore_or_initialize(st) == 2
        np.testing.assert_array_equal(st["hot"].numpy(),
                                      np.full(4, 2.0, np.float32))

    def test_plain_and_dedupe_restores_agree(self, tmp_path):
        a = CheckpointManager(str(tmp_path / "plain"))
        b = CheckpointManager(str(tmp_path / "cas"),
                              dedupe_chunks=True)
        a.save(1, _state(1), block=True)
        b.save(1, _state(1), block=True)
        sa, sb = _state(0), _state(0)
        a.restore(sa, step=1)
        b.restore(sb, step=1)
        for k in sa:
            np.testing.assert_array_equal(sa[k].numpy(),
                                          sb[k].numpy(), err_msg=k)


class TestRestoreTargetLayout:
    def test_degree1_layout_restore_bit_identical(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, _state(1), block=True)
        st = _state(0)
        step = mgr.restore_or_initialize(
            st, target_layout={"frozen": Layout.tp_sharded(2, 0, 1),
                               "hot": Layout.tp_sharded(1, 0, 1)})
        assert step == 1
        np.testing.assert_array_equal(st["frozen"].numpy(),
                                      np.full((8, 8), 3.25, np.float32))
        np.testing.assert_array_equal(st["hot"].numpy(),
                                      np.full(4, 1.0, np.float32))

    def test_unknown_name_and_bad_shape_raise(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, _state(1), block=True)
        st = _state(0)
        with pytest.raises(KeyError):
            mgr.restore(st, step=1,
                        target_layout={"nope": Layout.tp_sharded(1, 0, 1)})
        with pytest.raises(ValueError):
            mgr.restore(st, step=1,
                        target_layout={"hot": Layout.tp_sharded(1, 0, 3)})
