"""``gather_blocks`` / ``scatter_blocks``: the per-layer KV cache seen
as the stacked ``(L, n, BS, KH, D)`` frame of some blocks, held to NumPy
indexing of the stacked array that the engine used to keep."""
import jax
import numpy as np
import pytest

from paddle_tpu.distributed.redistribute import Layout
from paddle_tpu.serving.kv_blocks import gather_blocks, scatter_blocks

L, NB, BS, KH, D = 3, 12, 4, 4, 8

# ids as the callers send them: a swapped-out table in allocation order,
# an export of a table the free list handed out backwards, COW sources
# that two sharers copy from (repeated), one tail block, nothing at all
CASES = {
    "table": [2, 3, 7],
    "out_of_order": [9, 0, 5, 4],
    "repeated_sources": [6, 6, 1],
    "one_block": [11],
    "no_blocks": [],
}


@pytest.mark.parametrize("tp", [1, 4], ids=["one_device", "tp4"])
@pytest.mark.parametrize("case", list(CASES))
def test_block_frames_match_numpy_indexing_of_the_stacked_cache(case, tp):
    ids = np.asarray(CASES[case], np.int32)
    rng = np.random.default_rng(len(ids) + tp)
    stacked = rng.standard_normal((L, NB, BS, KH, D)).astype(np.float32)
    sharding = (Layout.tp_sharded(4, 2, tp).named_sharding(
        tuple(jax.devices()[:tp])) if tp > 1 else None)
    caches = tuple(jax.device_put(layer, sharding) for layer in stacked)

    frame = np.asarray(gather_blocks(caches, ids))
    np.testing.assert_array_equal(frame, stacked[:, ids])

    # write the frame to other blocks, distinct as every caller makes
    # them (the tiers dedupe, last writer wins): the stacked array's
    # fancy assignment, every other block untouched, sharding kept
    dst = np.asarray([(int(i) + 5) % NB for i in dict.fromkeys(ids.tolist())],
                     np.int32)
    values = frame[:, :len(dst)]
    want = stacked.copy()
    want[:, dst] = values
    out = scatter_blocks(caches, dst, values)
    assert isinstance(out, tuple) and len(out) == L
    np.testing.assert_array_equal(np.stack([np.asarray(c) for c in out]),
                                  want)
    if sharding is not None:
        assert all(c.sharding.is_equivalent_to(sharding, c.ndim)
                   for c in out)
    # round trip: what was written reads back, in the order written
    np.testing.assert_array_equal(np.asarray(gather_blocks(out, dst)),
                                  values)
    # the caches were donated to the write
    assert all(c.is_deleted() for c in caches)
