"""The K/V block frame's one owner (``serving/kv_frames.py``).

* the wire codec: both exports lay a payload out the same way (K shard
  frames, then V shard frames, CRC-32 over the whole) under the same
  meta keys, and both imports refuse the same malformed ships, cleanly
  and before a block is claimed;
* the host pool's one write path: a swap spill and a tier demote that
  land in the same host slot in one iteration leave the later writer's
  bytes there, whichever came first.
"""
import types
import zlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

PROMPT = list(range(1, 14))      # three full blocks at bs=4, one partial


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    return model


def _ecfg(**kw):
    kw.setdefault("block_size", 4)
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("drain_grace_s", 0.0)
    return EngineConfig(**kw)


@pytest.fixture(scope="module")
def source(tiny_model):
    """An engine holding ``PROMPT`` twice: live mid-request (for
    ``export_kv``) and committed to the trie (for ``export_prefix``)."""
    eng = LLMEngine(tiny_model, _ecfg())
    eng.add_request("src", PROMPT, sampling=SamplingParams(
        max_new_tokens=8))
    eng.step()                       # prefill commits + first token
    assert eng.get_request("src").num_cached > 0
    return eng


def _exports(eng):
    deep = max(eng.prefix_digest()["h"], key=eng.prefix_digest()["h"].get)
    return {"import_kv": eng.export_kv("src"),
            "import_prefix": eng.export_prefix(deep)}


def _host_frame(eng, table):
    """The ``(L, n, BS, KH, D)`` K and V frames of device blocks
    ``table``, read off the per-layer caches without the codec."""
    return tuple(np.stack([np.asarray(c)[np.asarray(table)] for c in cs])
                 for cs in (eng._kcs, eng._vcs))


def test_both_exports_pin_the_frame_layout(source):
    eng = source
    want_keys = {
        "import_kv": ["tokens_covered", "blocks", "block_size", "shape",
                      "dtype", "k_bytes", "layout", "crc32"],
        "import_prefix": ["chain_hash", "tokens", "blocks", "block_size",
                          "shape", "dtype", "k_bytes", "layout", "crc32"],
    }
    bm = eng.block_manager
    for method, (meta, payload) in _exports(eng).items():
        assert list(meta) == want_keys[method]
        if method == "import_kv":
            table = bm.export_blocks("src", meta["tokens_covered"])
        else:
            table = bm.prefix_blocks_by_hash(meta["chain_hash"])[1]
        k, v = eng.kv_frames.fetch(table)
        k_ref, v_ref = _host_frame(eng, table)
        np.testing.assert_array_equal(k, k_ref)
        np.testing.assert_array_equal(v, v_ref)
        lay = eng.kv_layout
        k_bytes = b"".join(s.tobytes() for s in lay.shards(k))
        assert payload == k_bytes + b"".join(s.tobytes()
                                             for s in lay.shards(v))
        assert meta["k_bytes"] == len(k_bytes)
        assert meta["crc32"] == zlib.crc32(payload) & 0xFFFFFFFF
        assert meta["shape"] == list(k.shape)
        assert meta["blocks"] == len(table) == k.shape[1]
        assert meta["block_size"] == 4 and meta["dtype"] == str(k.dtype)
        assert meta["layout"] == lay.to_meta()


def _flip_first_byte(payload):
    bad = bytearray(payload)
    bad[0] ^= 0xFF
    return bytes(bad)


# (meta edit, payload edit, the word the ValueError names)
_REJECTIONS = {
    "block_size": (lambda m: {**m, "block_size": 8}, None, "block_size"),
    "shape": (lambda m: {**m, "blocks": 99}, None, "shape"),
    "dtype": (lambda m: {**m, "dtype": "float16"}, None, "dtype"),
    "byte_count": (None, lambda p: p[:-4], r"payload \d+B"),
    "checksum": (None, _flip_first_byte, "checksum"),
}


@pytest.mark.parametrize("fault", sorted(_REJECTIONS))
@pytest.mark.parametrize("method", ["import_kv", "import_prefix"])
def test_import_rejects_malformed_ship(tiny_model, source, method, fault):
    meta, payload = _exports(source)[method]
    edit_meta, edit_payload, word = _REJECTIONS[fault]
    meta = edit_meta(meta) if edit_meta else meta
    payload = edit_payload(payload) if edit_payload else payload
    dst = LLMEngine(tiny_model, _ecfg())
    with pytest.raises(ValueError, match=word):
        if method == "import_kv":
            dst.import_kv("dst", list(source.get_request("src").tokens),
                          sampling=SamplingParams(max_new_tokens=4),
                          meta=meta, payload=payload)
        else:
            dst.import_prefix(meta=meta, payload=payload)
    bm = dst.block_manager
    assert bm.num_free_blocks == bm.num_blocks   # nothing was claimed
    bm.check_invariants()
    assert "dst" not in dst._requests and not dst.has_unfinished()


@pytest.mark.parametrize("spill_last", [False, True])
def test_spill_and_demote_to_one_host_slot_last_writer_wins(tiny_model,
                                                            spill_last):
    """A host slot written by a swap spill and by a tier demote in the
    same iteration holds the bytes of whichever came later."""
    eng = LLMEngine(tiny_model, _ecfg(
        num_blocks=16, swap_mode="host", kv_tiers={"num_host_blocks": 4}))
    eng.add_request("a", PROMPT, sampling=SamplingParams(max_new_tokens=2))
    eng.step()
    bm = eng.block_manager
    # the partial tail block: written, never registered, so never the
    # block a demote picks — the spill's source
    spilled = bm.block_table("a")[-1]
    while eng.has_unfinished():
        eng.step()
    eng.release_request("a")
    frames = eng.kv_frames
    frames.apply_tier_moves()
    victim = types.SimpleNamespace(request_id="victim")
    slot = bm._host_free[-1]           # the slot the next demote claims
    if not spill_last:
        frames.copy_out(victim, [spilled], [slot])
    assert bm.demote_cached_free(1) == 1
    (_, demoted, demote_slot), = bm._tier_moves
    assert demote_slot == slot and demoted != spilled
    if spill_last:
        frames.copy_out(victim, [spilled], [slot])
    frames.apply_tier_moves()
    want = _host_frame(eng, [spilled if spill_last else demoted])
    other = _host_frame(eng, [demoted if spill_last else spilled])
    assert not np.array_equal(want[0], other[0])
    got = frames.fetch([bm.virtual_of(slot)])   # the host pool's read path
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
