"""References for the in-graph sampler's filter (tests only).

* :func:`filtered_probs_sorted` — ``paddle_tpu.ops.sampling.filtered_probs``
  as it stood before the cut-offs were found by threshold selection: two
  argsorts and a sort of the whole vocabulary, float32. Moved here
  unchanged; the rule it states is the specification.
* :func:`sample_or_verify_sorted` — the program's ``sample_or_verify``
  with that filter in place of its own: same keys in, the streams must be
  the same.
* :func:`oracle_probs` — the same rule in float64 NumPy with a STABLE
  descending order (of equal probabilities the lower index first), which
  ``LLMEngine._sample``'s default argsort leaves open.
"""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import sampling


def filtered_probs_sorted(logits, temperature, top_k, top_p):
    lg = logits.astype(jnp.float32)
    v = lg.shape[-1]
    greedy = temperature <= 0.0
    t = jnp.where(greedy, 1.0, temperature)[:, None]
    x = lg / t
    x = x - jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # top-k: zero everything below the k-th largest probability
    desc = jnp.sort(p, axis=-1)[:, ::-1]
    k_eff = jnp.where((top_k > 0) & (top_k < v), top_k, v)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    p = jnp.where(p >= kth, p, 0.0)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    # top-p: keep the smallest descending-order prefix whose cumulative
    # mass reaches top_p (same keep_n = searchsorted(csum, top_p) + 1
    # rule as the host oracle)
    order = jnp.argsort(-p, axis=-1)
    sp = jnp.take_along_axis(p, order, axis=-1)
    csum = jnp.cumsum(sp, axis=-1)
    keep_n = jnp.sum((csum < top_p[:, None]).astype(jnp.int32),
                     axis=-1) + 1
    rank = jnp.argsort(order, axis=-1)
    p = jnp.where(rank < keep_n[:, None], p, 0.0)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(jnp.argmax(lg, axis=-1), v, dtype=p.dtype)
    return jnp.where(greedy[:, None], onehot, p)


def sample_or_verify_sorted(*args):
    """``sampling.sample_or_verify`` traced with the sort-based filter."""
    own = sampling.filtered_probs
    sampling.filtered_probs = filtered_probs_sorted
    try:
        return sampling.sample_or_verify(*args)
    finally:
        sampling.filtered_probs = own


def oracle_probs(logits, temperature, top_k, top_p):
    """One row, float64. Returns ``(p, before)``: the filtered
    distribution and, per entry, the nucleus mass that precedes it in the
    stable descending order of the top-k-filtered row (what decides
    whether the entry is kept: ``before < top_p``)."""
    logits = np.asarray(logits, np.float64)
    if temperature <= 0.0:
        p = np.zeros_like(logits)
        p[np.argmax(logits)] = 1.0
        return p, np.zeros_like(logits)
    x = logits / np.float64(np.float32(temperature))
    x -= x.max()
    p = np.exp(x)
    p /= p.sum()
    if 0 < top_k < p.size:
        kth = np.sort(p)[-top_k]
        p = np.where(p >= kth, p, 0.0)
        p /= p.sum()
    order = np.argsort(-p, kind="stable")
    csum = np.cumsum(p[order])
    before = np.empty_like(p)
    before[order] = csum - p[order]
    if top_p < 1.0:
        p = np.where(before < np.float64(np.float32(top_p)), p, 0.0)
        p /= p.sum()
    return p, before
