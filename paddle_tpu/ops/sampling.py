"""In-graph token sampling + speculative-verify (serving hot path).

Everything here runs INSIDE the engine's compiled step, so a sampled
decode iteration ships B int32 tokens (plus the per-slot RNG keys) to
host — never the B×vocab logits. Three layers:

* :func:`filtered_probs` — fused temperature / top-k / top-p transform
  of a batch of logit rows into sampling distributions. Greedy rows
  (``temperature <= 0``) become an EXACT one-hot at ``argmax(logits)``
  (first-occurrence tie-breaking, matching ``np.argmax``), which keeps
  the greedy path bit-identical to the host oracle and lets one code
  path serve mixed greedy/sampled batches. The cut-offs are VALUES
  found by threshold selection (:func:`_select`), not positions in a
  sorted row: the k-th largest probability is the largest bit pattern
  that k entries reach, the nucleus boundary the largest one whose
  entries-at-or-above hold ``top_p`` of the mass, and of the entries
  equal to the boundary the first by index stay. Nothing sorts,
  argsorts or permutes the vocabulary (a sort of 32 x 200,064 floats
  was three quarters of a decode step); the rule, ties included, and
  so the token streams are the sort-based filter's, which lives on as
  the tests' reference (``tests/refs/sampling_sort_ref.py``). What may
  differ is float32 summation order: an entry of the boundary's tie
  run whose preceding mass lies within a few 1e-6 of ``top_p``.
* :func:`sample_tokens` — one categorical draw per slot from its own
  PRNG key (the per-request stream the engine persists), returning the
  advanced keys alongside the tokens.
* :func:`sample_or_verify` — the general form: each slot carries
  ``n_draft`` speculative tokens proposed by a draft model and ``R =
  logits.shape[1]`` gathered logit rows (the last R packed positions of
  the slot's ragged row). Standard rejection sampling runs per slot:
  draft token i is accepted with probability ``p_target(t_i)`` (the
  draft proposes greedily, i.e. ``q`` is a point mass, so ``min(1,
  p/q) = p(t_i)``), a rejection emits one corrected token drawn from
  ``p`` with ``t_i`` masked out (``norm(max(0, p - q))`` for a point
  mass), and a fully-accepted draft earns one bonus token from the last
  row. The emitted-token marginal is EXACTLY the target distribution at
  every position (the rejection-sampling guarantee, pinned against the
  CPU oracle by tests/test_spec_decode.py); a greedy target degenerates
  to exact prefix match, so speculative greedy decode is token-identical
  to the non-speculative engine. ``n_draft == 0`` rows reduce to plain
  :func:`sample_tokens` — ONE code path runs mixed normal/verify
  batches.

RNG-stream contract: every call advances each slot's key by a FIXED
number of splits (``2*(R-1) + 1``), independent of the slot's data, so
a request's stream position is a pure function of how many engine steps
emitted for it — what makes fleet drain hand-off (which carries the
key) bit-identical to an uninterrupted engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["filtered_probs", "sample_tokens", "sample_or_verify"]


def _select(bits, weights, target, width=31):
    """Per row, the largest ``t`` below ``2**width`` with ``sum(weights
    where bits >= t) >= target`` (0 where no ``t`` reaches it). ``t`` is
    built from its top bit down, one compare-and-reduce pass over (S, V)
    a bit: a fixed trip count whatever the data (NaNs included), no
    ordering of the row. Non-negative floats order as their uint32 bit
    patterns do, so over a row of probabilities ``t`` is one of them."""
    def one_bit(i, t):
        cand = t | (jnp.uint32(1 << (width - 1)) >> i.astype(jnp.uint32))
        reached = jnp.sum(jnp.where(bits >= cand[:, None], weights, 0),
                          axis=-1)
        return jnp.where(reached >= target, cand, t)

    return jax.lax.fori_loop(
        0, width, one_bit, jnp.zeros(bits.shape[:1], jnp.uint32))


def _bits(p):
    return jax.lax.bitcast_convert_type(p, jnp.uint32)


def filtered_probs(logits, temperature, top_k, top_p):
    """Per-row sampling distributions: ``logits`` (S, V); ``temperature``
    (S,) float (``<= 0`` = greedy one-hot); ``top_k`` (S,) int (0 = off);
    ``top_p`` (S,) float (1.0 = off). Returns (S, V) probabilities.

    The host oracle's (``LLMEngine._sample``) rule in f32 — temperature
    softmax; top-k keeps every ``p >= kth`` (all ties at the k-th
    largest), renormalized; top-p keeps the smallest stable
    descending-order prefix whose mass reaches ``top_p`` (of equal
    probabilities the lower index first), renormalized — with both
    cut-offs found by :func:`_select`, never by ordering the row."""
    lg = logits.astype(jnp.float32)
    v = lg.shape[-1]
    greedy = temperature <= 0.0
    t = jnp.where(greedy, 1.0, temperature)[:, None]
    x = lg / t
    x = x - jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # top-k: kth = the largest value that k entries reach (k = V, the
    # row's minimum, when top-k is off)
    k_eff = jnp.where((top_k > 0) & (top_k < v), top_k, v)
    bits = _bits(p)
    kth = _select(bits, 1, k_eff)
    p = jnp.where(bits >= kth[:, None], p, 0.0)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # top-p: the boundary value t* is the largest whose entries-at-or-
    # above hold top_p of the mass; everything above it stays, and of
    # the run equal to it the first m by index, m = what the mass above
    # still lacks, in entries of t*
    bits = _bits(p)
    cut = _select(bits, p, top_p)
    above = bits > cut[:, None]
    tie = bits == cut[:, None]
    lacks = top_p - jnp.sum(jnp.where(above, p, 0.0), axis=-1)
    n_tie = jnp.sum(tie, axis=-1)
    m = jnp.ceil(lacks / jax.lax.bitcast_convert_type(cut, jnp.float32))
    m = jnp.clip(m, 1.0, n_tie.astype(jnp.float32)).astype(jnp.int32)
    # the m-th tie by index = the (n_tie - m + 1)-th from the top
    pos = jnp.where(tie, jnp.arange(1, v + 1, dtype=jnp.uint32), 0)
    last = _select(pos, 1, n_tie - m + 1, width=v.bit_length())
    keep = above | (tie & (pos <= last[:, None])) | (top_p >= 1.0)[:, None]
    p = jnp.where(keep, p, 0.0)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(jnp.argmax(lg, axis=-1), v, dtype=p.dtype)
    return jnp.where(greedy[:, None], onehot, p)


def _split_rows(keys):
    """Advance a (S, 2) uint32 key batch one split: returns
    ``(chain_keys, draw_keys)``, each (S, 2)."""
    ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return ks[:, 0], ks[:, 1]


def sample_or_verify(logits, draft_tokens, n_draft, keys, temperature,
                     top_k, top_p):
    """Rejection-sample ``n_draft`` proposed tokens per slot and draw the
    corrected/bonus token, in one fused pass.

    ``logits`` (S, R, V): row j is the target distribution for the
    slot's draft token j (relative to its own draft window — the engine
    gathers the LAST R packed positions of each row, so a slot with
    ``d < R-1`` drafts finds its window right-aligned: verify rows start
    at index ``R-1-d``). ``draft_tokens`` (S, R-1) int32 (garbage past
    ``n_draft``); ``n_draft`` (S,) int32 in [0, R-1]; ``keys`` (S, 2)
    uint32; sampling params (S,) as in :func:`filtered_probs`.

    Returns ``(tokens (S, R) int32, n_emit (S,) int32, new_keys (S, 2)
    uint32)`` — tokens[:, :n_emit] are valid: the accepted draft prefix
    plus exactly one corrected-or-bonus token (``n_emit = accepted +
    1``)."""
    s, r, v = logits.shape
    rows = jnp.arange(s)
    out = jnp.zeros((s, r), jnp.int32)
    n_emit = jnp.zeros((s,), jnp.int32)
    done = jnp.zeros((s,), bool)
    keys = keys.astype(jnp.uint32)
    for j in range(r - 1):
        idx = jnp.clip((r - 1) - n_draft + j, 0, r - 1)
        lg = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        p = filtered_probs(lg, temperature, top_k, top_p)
        t = jnp.clip(draft_tokens[:, j], 0, v - 1)
        p_t = jnp.take_along_axis(p, t[:, None], axis=-1)[:, 0]
        keys, sub = _split_rows(keys)
        u = jax.vmap(jax.random.uniform)(sub)
        keys, sub2 = _split_rows(keys)
        # corrected draw: p with the rejected proposal masked out —
        # norm(max(0, p - q)) for the greedy draft's point-mass q;
        # categorical takes unnormalized log-mass, so no renorm (and no
        # 0/0) is needed. Computed unconditionally, used only on reject.
        p_rej = jnp.where(jnp.arange(v)[None, :] == t[:, None], 0.0, p)
        corr = jax.vmap(jax.random.categorical)(sub2, jnp.log(p_rej))
        active = (~done) & (j < n_draft)
        acc = u < p_t
        emit = jnp.where(acc, t, corr).astype(jnp.int32)
        out = out.at[:, j].set(jnp.where(active, emit, out[:, j]))
        n_emit = jnp.where(active, n_emit + 1, n_emit)
        done = done | (active & ~acc)
    # bonus (fully-accepted verify rows) == the plain sampling draw
    # (n_draft == 0 rows): one token from the last gathered position
    p = filtered_probs(logits[:, r - 1], temperature, top_k, top_p)
    keys, sub = _split_rows(keys)
    bonus = jax.vmap(jax.random.categorical)(sub, jnp.log(p))
    active = ~done
    slot = jnp.clip(n_emit, 0, r - 1)
    cur = out[rows, slot]
    out = out.at[rows, slot].set(
        jnp.where(active, bonus.astype(jnp.int32), cur))
    n_emit = jnp.where(active, n_emit + 1, n_emit)
    return out, n_emit, keys


def sample_tokens(logits, keys, temperature, top_k, top_p):
    """One sampled token per row: ``logits`` (S, V), ``keys`` (S, 2)
    uint32. Returns ``(tokens (S,) int32, new_keys (S, 2) uint32)`` —
    the ``n_draft == 0`` special case of :func:`sample_or_verify`."""
    s = logits.shape[0]
    out, _, keys2 = sample_or_verify(
        logits[:, None, :], jnp.zeros((s, 0), jnp.int32),
        jnp.zeros((s,), jnp.int32), keys, temperature, top_k, top_p)
    return out[:, 0], keys2
