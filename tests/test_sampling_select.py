"""The in-graph sampler's filter finds its top-k / top-p cut-offs by
threshold selection (``paddle_tpu.ops.sampling._select``); the RULE is the
sort-based one's, ties included. Held here against both references of
``tests/refs/sampling_sort_ref.py``: the sort-based float32 filter it
replaced (same kept set, same probabilities, same streams for the same
keys) and a float64 oracle with a stable order. Logits are rounded to
bfloat16 as the models' heads give them, so equal values are the common
case at a real vocabulary, not a corner."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.sampling import (filtered_probs, sample_or_verify,
                                     sample_tokens)
from tests.refs.sampling_sort_ref import (filtered_probs_sorted,
                                          oracle_probs,
                                          sample_or_verify_sorted)

INTERNLM = (0.8, 50, 0.95)      # internlm2-1.8b.chat-c16's sampled requests
PHI4 = (0.6, 0, 0.95)           # phi4-mini-flash.reason-c32's (the card's)
GREEDY = (0.0, 0, 1.0)
V_INTERNLM, V_PHI4 = 92_544, 200_064
# a boundary nearer than this to top_p is float32 summation order, not rule
EXACT_GAP, ROUNDING = 1e-4, 4e-6


def _bf16(x):
    return np.array(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                    .astype(jnp.float32))


def _peaked(seed, s, v, scale=4.0):
    return _bf16(np.random.default_rng(seed).normal(size=(s, v)) * scale)


def _params(rows):
    t, k, p = zip(*rows)
    return (np.asarray(t, np.float32), np.asarray(k, np.int32),
            np.asarray(p, np.float32))


def _tie_run_rows():
    """V = 32. Row 0: the nucleus boundary falls inside a run of ten equal
    probabilities that starts at index 3 (the lower indices are kept).
    Row 1: the 4th largest value is one of five equal ones (top-k 4 keeps
    all seven at or above it), top-p off. Row 2: both at once."""
    lg = np.full((3, 32), -9.0, np.float32)
    lg[0, [20, 1]] = 3.0, 2.0
    lg[0, [3, 5, 6, 9, 11, 14, 17, 22, 27, 30]] = 1.0
    lg[1, [8, 2]] = 4.0, 3.5
    lg[1, [0, 7, 13, 19, 31]] = 2.0
    lg[1, [4, 5, 6]] = 1.0
    lg[2] = lg[1]
    lg[2, [10, 12, 15]] = 1.0
    return lg, [(1.0, 0, 0.8), (1.0, 4, 1.0), (0.7, 4, 0.9)]


def _grid(v):
    """Every top-k of the issue's list against every top-p, greedy rows
    mixed in."""
    rows = [(0.9, k, p) for k in (0, 1, 50, v - 1, v, v + 7)
            for p in (1.0, 0.95, 1e-6)]
    rows[4:4] = [GREEDY]
    rows.append(GREEDY)
    return rows


def _exact_cases():
    yield "small-v-grid", _peaked(1, 20, 32, 2.0), _grid(32)
    yield "small-v-1000-grid", _peaked(2, 20, 1000), _grid(1000)
    lg, rows = _tie_run_rows()
    yield "tie-runs", lg, rows
    yield ("internlm-cell", _peaked(3, 4, V_INTERNLM, 5.0),
           [INTERNLM, GREEDY, INTERNLM, GREEDY])
    yield ("phi4-cell", _peaked(3, 4, V_PHI4, 6.0),
           [PHI4, GREEDY, GREEDY, PHI4])
    yield ("internlm-width-top-k", _peaked(5, 6, V_INTERNLM, 6.0),
           [(0.8, k, 0.95) for k in (0, 1, 50, V_INTERNLM - 1, V_INTERNLM,
                                     V_INTERNLM + 7)])
    yield ("phi4-width-top-p", _peaked(6, 4, V_PHI4, 6.0),
           [(0.6, 0, 1.0), (0.6, 0, 1e-6), (0.6, 50, 1.0), (1.0, 7, 0.5)])


EXACT = {name: (lg, rows) for name, lg, rows in _exact_cases()}


def _kept_is_settled(p, before, temperature, top_p):
    """True where no entry's preceding mass (``oracle_probs``'s) lies
    within EXACT_GAP of top_p: the float64 kept set is then float32's."""
    live = before[(p > 0) | (before >= top_p)]
    return top_p >= 1.0 or temperature <= 0.0 or \
        np.abs(live[live > 0] - np.float64(np.float32(top_p))).min() \
        > EXACT_GAP


@pytest.mark.parametrize("name", list(EXACT))
def test_kept_set_and_probabilities_equal_both_references(name):
    lg, rows = EXACT[name]
    t, k, p = _params(rows)
    new = np.asarray(jax.jit(filtered_probs)(lg, t, k, p))
    old = np.asarray(jax.jit(filtered_probs_sorted)(lg, t, k, p))
    for i, row in enumerate(rows):
        ref, before = oracle_probs(lg[i], *row)
        assert _kept_is_settled(ref, before, row[0], row[2]), (i, row)
        seen = ref > 1e-37              # float32's exp gives the rest as 0
        np.testing.assert_array_equal((new[i] > 0)[seen], (ref > 0)[seen],
                                      err_msg=str(row))
        np.testing.assert_allclose(new[i], ref, rtol=2e-5, atol=1e-9)
        if np.array_equal((old[i] > 0)[seen], (ref > 0)[seen]):
            np.testing.assert_array_equal(new[i], old[i], err_msg=str(row))
        else:
            # the one place the sort-based filter leaves the rule: with
            # top-p off its float32 cumsum can reach 1.0 before the row
            # ends, and the tail behind that is dropped. The rule (and
            # the float64 ``oracle_probs``) keeps everything.
            assert row[2] >= 1.0 and (old[i] > 0).sum() < (ref > 0).sum()
        if row[0] <= 0.0:
            assert set(np.unique(new[i])) == {0.0, 1.0}
            assert np.argmax(new[i]) == np.argmax(lg[i])


def test_tie_runs_keep_the_lower_indices_and_every_tie_at_kth():
    lg, rows = _tie_run_rows()
    t, k, p = _params(rows)
    kept = np.asarray(filtered_probs(lg, t, k, p)) > 0
    # 0.8 of the mass = both single entries (0.503) and the first six of
    # the ten equal ones (0.0497 each)
    assert np.flatnonzero(kept[0]).tolist() == [1, 3, 5, 6, 9, 11, 14, 20]
    assert np.flatnonzero(kept[1]).tolist() == [0, 2, 7, 8, 13, 19, 31]
    # top-k 4 leaves those seven; 0.9 of their mass = the two single
    # entries (0.838) and the first two of the five equal ones
    assert np.flatnonzero(kept[2]).tolist() == [0, 2, 7, 8]


def _verify_inputs(seed, s, r, v, rows):
    rng = np.random.default_rng(seed)
    lg = _bf16(rng.normal(size=(s, r, v)) * 3.0)
    n_draft = (np.arange(s) % r).astype(np.int32)
    # drafts the target is likely to accept (a greedy row accepts them
    # all), so every outcome occurs; a slot's window is right-aligned
    drafts = np.zeros((s, r - 1), np.int32)
    for i, d in enumerate(n_draft):
        drafts[i, :d] = np.argmax(lg[i, r - 1 - d:r - 1], axis=-1)
    keys = rng.integers(0, 2**32, size=(s, 2), dtype=np.uint32)
    return (lg, drafts, n_draft, keys) + _params(rows)


def _stream_cases():
    for name, (lg, rows) in EXACT.items():
        s = lg.shape[0]
        keys = np.random.default_rng(7).integers(
            0, 2**32, size=(s, 2), dtype=np.uint32)
        yield name, (lg[:, None, :], np.zeros((s, 0), np.int32),
                     np.zeros((s,), np.int32), keys) + _params(rows)
    yield "verify-r3-small-v", _verify_inputs(
        8, 12, 3, 32, [INTERNLM, PHI4, GREEDY, (1.0, 5, 0.7)] * 3)
    yield "verify-r3-internlm-width", _verify_inputs(
        9, 3, 3, V_INTERNLM, [INTERNLM, GREEDY, INTERNLM])


STREAMS = dict(_stream_cases())


@pytest.mark.parametrize("name", list(STREAMS))
def test_same_keys_give_the_sort_based_samplers_streams(name):
    args = STREAMS[name]
    toks, n_emit, keys = jax.jit(sample_or_verify)(*args)
    rtoks, rn_emit, rkeys = jax.jit(sample_or_verify_sorted)(*args)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(rkeys))
    np.testing.assert_array_equal(np.asarray(n_emit), np.asarray(rn_emit))
    # a row whose distribution the sort-based filter cut short (top-p off,
    # see above) may draw from the tail it dropped; every other row's
    # tokens are equal, not close
    lg, top_p = args[0], args[6]
    old = np.asarray(jax.jit(filtered_probs_sorted)(lg[:, -1], *args[4:]))
    new = np.asarray(jax.jit(filtered_probs)(lg[:, -1], *args[4:]))
    same = np.all((old > 0) == (new > 0), axis=-1)
    assert np.all(same | (top_p >= 1.0)) and same.sum() >= len(same) // 2
    np.testing.assert_array_equal(np.asarray(toks)[same],
                                  np.asarray(rtoks)[same])
    if lg.shape[1] > 1:
        assert same.all() and len(set(np.asarray(n_emit).tolist())) > 1


def _flat_cases():
    rng = np.random.default_rng(11)
    # all logits equal: one tie run V long
    yield "phi4-all-equal", np.zeros((V_PHI4,), np.float32), PHI4
    # nearly flat: in [2, 4) bfloat16 steps by 1/64, which leaves a few
    # dozen distinct values and runs thousands long
    yield "phi4-near-flat", _bf16(3 + rng.normal(size=V_PHI4) * 0.05), PHI4
    yield ("internlm-near-flat",
           _bf16(3 + rng.normal(size=V_INTERNLM) * 0.05), INTERNLM)
    yield ("internlm-near-flat-no-top-k",
           _bf16(3 + rng.normal(size=V_INTERNLM) * 0.05), (0.8, 0, 0.95))


FLAT = {name: (lg, row) for name, lg, row in _flat_cases()}


@pytest.mark.parametrize("name", list(FLAT))
def test_flat_rows_differ_from_float64_only_inside_the_boundary_tie_run(name):
    lg, row = FLAT[name]
    t, k, p = _params([row])
    probs = jax.jit(filtered_probs)(lg[None], t, k, p)
    kept = np.asarray(probs)[0] > 0
    ref, before = oracle_probs(lg, *row)
    top_p = np.float64(np.float32(row[2]))
    # float64's boundary entry, and the run of values equal to it
    boundary = np.flatnonzero(ref > 0)[np.argmax(before[ref > 0])]
    run = lg == lg[boundary]
    if row[1] == 0:
        assert run.sum() > 1000 and 0.9 < kept.mean() < 0.96
    unsure = run & (np.abs(before - top_p) <= ROUNDING)
    assert unsure.sum() <= np.ceil(2 * ROUNDING * lg.size) + 1
    np.testing.assert_array_equal(kept[~unsure], (ref > 0)[~unsure])
    # inside the run the lower indices are kept first
    in_run = np.flatnonzero(run)
    n_in = kept[in_run].sum()
    assert kept[in_run[:n_in]].all() and not kept[in_run[n_in:]].any()
    # 4,096 draws of the sampler's own kind stay inside
    keys = np.random.default_rng(12).integers(
        0, 2**32, size=(4096, 2), dtype=np.uint32)
    logp = jnp.log(probs[0])
    toks = np.asarray(jax.jit(lambda ks: jax.lax.map(
        lambda key: jax.random.categorical(key, logp), ks,
        batch_size=64))(keys))
    assert ((ref > 0) | unsure)[toks].all() and kept[toks].all()
    assert len(set(toks.tolist())) > 3000 or row[1] > 0


def test_greedy_and_sampled_rows_share_one_call_at_a_real_vocabulary():
    lg = _peaked(13, 4, V_PHI4)
    keys = np.random.default_rng(14).integers(0, 2**32, size=(4, 2),
                                              dtype=np.uint32)
    t, k, p = _params([GREEDY, PHI4, GREEDY, INTERNLM])
    toks, _ = jax.jit(sample_tokens)(lg, keys, t, k, p)
    toks = np.asarray(toks)
    assert toks[0] == np.argmax(lg[0]) and toks[2] == np.argmax(lg[2])
    for i in (1, 3):
        assert oracle_probs(lg[i], t[i], k[i], p[i])[0][toks[i]] > 0


def test_a_row_of_nans_neither_hangs_nor_spreads():
    lg = _peaked(15, 3, 1000)
    lg[1] = np.nan
    t, k, p = _params([INTERNLM, INTERNLM, PHI4])
    clean = np.asarray(filtered_probs(np.delete(lg, 1, 0), t[[0, 2]],
                                      k[[0, 2]], p[[0, 2]]))
    got = np.asarray(filtered_probs(lg, t, k, p))
    np.testing.assert_array_equal(got[[0, 2]], clean)
    assert np.isnan(got[1]).all()


# -- structure: what the lowered sampler may not hold -------------------------
def _big_index_ops(text, least):
    """(op, element counts of its tensor types) of every sort, and of
    every scatter / gather that touches ``least`` elements or more."""
    found = []
    for m in re.finditer(
            r'stablehlo\.(sort|scatter|gather|dynamic_gather)\b', text):
        # the op's type signature ends its statement: the first
        # `-> tensor<...>` after it that is followed by a line end
        sig = re.search(r'\)\s*:\s*\(([^\n]*?)\)\s*->\s*[^\n]*\n',
                        text[m.end():])
        sizes = [int(np.prod([int(d) for d in dims.split("x") if d]))
                 for dims in re.findall(r'tensor<((?:\d+x)*)[a-z]\w*>',
                                        sig.group(1))]
        if m.group(1) == "sort" or max(sizes, default=0) >= least:
            found.append((m.group(1), sizes))
    return found


def test_lowered_sampler_holds_no_sort_and_no_vocabulary_sized_gather():
    s, v = 32, V_PHI4
    sds = jax.ShapeDtypeStruct
    args = (sds((s, 1, v), jnp.float32), sds((s, 0), jnp.int32),
            sds((s,), jnp.int32), sds((s, 2), jnp.uint32),
            sds((s,), jnp.float32), sds((s,), jnp.int32),
            sds((s,), jnp.float32))
    text = jax.jit(sample_or_verify).lower(*args).as_text()
    assert "top_k" not in text and "stablehlo.while" in text
    assert _big_index_ops(text, v) == []
    # the check itself sees what it is there to catch
    old = jax.jit(sample_or_verify_sorted).lower(*args).as_text()
    assert {op for op, _ in _big_index_ops(old, v)} >= {"sort"}
    # one traced body a selection (top-k, nucleus, tie index), not 31
    # unrolled copies of it
    alone = jax.jit(filtered_probs).lower(
        sds((s, v), jnp.float32), *args[4:]).as_text()
    assert len(re.findall(r'stablehlo\.while', alone)) == 3
