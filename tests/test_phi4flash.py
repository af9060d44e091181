"""Phi-4-mini-flash (SambaY + differential attention) held to the plain
reference: the model's whole-sequence forward, the ragged selective scan,
the padded-query route through the ragged attention op, and the serving
engine's three kinds of cache (full pool, window pool with release,
per-sequence state slots) through chunked prefill, preemption and row
reordering. Tiny widths with the published layout rule and ratios:
8 layers, window 8, block 4. Logits, not tokens, wherever the engine's
inputs can be replayed."""
import ast
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import phi4flash
from paddle_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention,
)
from paddle_tpu.ops.selective_scan import (
    ragged_causal_conv, ragged_selective_scan, selective_scan_step,
)
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.testing import faults
from refs import phi4flash_ref as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BS, WINDOW = 4, 8


def randomize(model, seed=0):
    """Norm weights and biases away from their 1/0 initial values, so a
    dropped bias or weight shows."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        a = np.asarray(p._data)
        if name.endswith(("norm1_w", "norm2_w", "final_norm.weight")):
            new = 1 + 0.1 * rng.standard_normal(a.shape)
        elif name.endswith(("norm1_b", "norm2_b", "conv_b",
                            "final_norm.bias")):
            new = 0.1 * rng.standard_normal(a.shape)
        else:
            continue
        p._data = jnp.asarray(new, a.dtype)


def ref_params(model):
    return {"embed": model.embed_tokens.weight._data,
            "norm_w": model.final_norm.weight._data,
            "norm_b": model.final_norm.bias._data,
            "layers": [lay.weights() for lay in model.layers]}


def ref_cfg(c):
    return dict(num_hidden_layers=c.num_hidden_layers,
                num_attention_heads=c.num_attention_heads,
                num_key_value_heads=c.num_key_value_heads,
                hidden_size=c.hidden_size, sliding_window=c.sliding_window,
                layer_norm_eps=c.layer_norm_eps)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    m.eval()
    randomize(m)
    return m


_REF_CACHE = {}


def ref_logits(model, tokens):
    """The reference's logits at every position of ``tokens``."""
    key = (id(model), tuple(tokens))
    if key not in _REF_CACHE:
        _REF_CACHE[key] = np.asarray(ref.forward(
            ref_params(model), np.asarray(tokens), ref_cfg(model.config)))
    return _REF_CACHE[key]


class LogitSpy:
    """Stands in for the engine's compiled step: before each dispatch,
    runs the model's ``forward_ragged`` (outside any jit, layer kind by
    layer kind) on the step's own inputs and the cache as it is, and keeps
    every live row's logits by request and context length."""

    def __init__(self, engine):
        self.engine, self.real = engine, engine._jstep_ragged
        self.logits = {}        # (request id, context length) -> (V,)
        self.rows = {}          # request id -> [(row index, slot), ...]
        engine._jstep_ragged = self

    def __call__(self, *args):
        ids, cache, tables, bt, cu, ctx, nseq = args[3:10]
        lg, _ = self.engine.model.forward_ragged(ids, cache, tables, bt,
                                                 cu, ctx, nseq)
        lg = np.asarray(lg)
        running = {self.engine.block_manager.state_slot(r.request_id):
                   r.request_id for r in self.engine.scheduler.running}
        for i in range(int(nseq)):
            rid = running[int(tables["slots"][i])]
            self.logits[(rid, int(ctx[i]))] = lg[i]
            self.rows.setdefault(rid, []).append(
                (i, int(tables["slots"][i])))
        return self.real(*args)


def serve(model, prompts, new_tokens, check=None, **ecfg):
    """Serve ``prompts`` ({id: tokens}) greedily; returns (generated by
    id, the spy, the engine). ``check(engine)`` runs after every step."""
    kw = dict(block_size=BS, max_num_seqs=4, max_model_len=96,
              max_batched_tokens=16)
    kw.update(ecfg)
    eng = LLMEngine(model, EngineConfig(**kw))
    spy = LogitSpy(eng)
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(max_new_tokens=new_tokens))
    gen = {}
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished:
                assert out.finish_reason == "length", out.finish_reason
                gen[out.request_id] = list(out.generated)
                eng.release_request(out.request_id)
        eng.block_manager.check_invariants()
        if check is not None:
            check(eng)
    return gen, spy, eng


def assert_against_reference(model, prompts, gen, spy, tol=2e-4):
    """Every row the engine computed that could yield a token (the last
    row of a prompt and every decode row) equals the reference's logits
    at that position of the request's own token history."""
    checked = 0
    for rid, prompt in prompts.items():
        tokens = list(prompt) + gen[rid]
        want = ref_logits(model, tokens)
        for c in range(len(prompt), len(tokens)):
            got = spy.logits[(rid, c)]
            err = np.abs(got - want[c - 1]).max()
            assert err <= tol * np.abs(want).max(), (rid, c, err)
            checked += 1
    return checked


def prompts_of(lengths, seed=5, vocab=160):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(0, vocab, n)]
            for i, n in enumerate(lengths)}


# -- the reference and its copy -------------------------------------------
def test_reference_copies_define_the_same_functions():
    def functions(path):
        with open(path) as f:
            tree = ast.parse(f.read())
        return {n.name: ast.dump(n) for n in tree.body
                if isinstance(n, ast.FunctionDef)}

    mine = functions(os.path.join(HERE, "refs", "phi4flash_ref.py"))
    theirs = functions(os.path.join(HERE, "..", "benchmark",
                                    "reference_phi4flash.py"))
    assert mine and mine == theirs


def test_layout_rule_at_published_depth():
    c = Phi4FlashConfig()
    kinds = [c.layer_kind(l) for l in range(32)]
    assert [ref.layer_kind(l, 32) for l in range(32)] == kinds
    assert kinds.count("mamba") == 9 and kinds.count("window") == 8
    assert kinds.count("gmu") == 7 and kinds.count("cross") == 7
    assert kinds[17] == "full" and kinds[16] == "mamba"
    assert c.mamba_dt_rank == 160 and c.d_inner == 5120
    assert c.lambda_init(3) == ref.lambda_init(3)


# -- (a) forward ----------------------------------------------------------
@pytest.mark.parametrize("length", [5, 8, 21])      # under, at, over window
def test_forward_logits_match_reference(model, length):
    ids = np.random.default_rng(length).integers(0, 160, (2, length))
    out = np.asarray(model(paddle.to_tensor(ids))._data)
    for b in range(2):
        want = ref_logits(model, [int(t) for t in ids[b]])
        assert np.abs(out[b] - want).max() <= 2e-5 * np.abs(want).max()


# -- (j) the ragged scan and its convolution ------------------------------
def _scan_case(seed, rows, e=24, n=4, spare=1):
    """rows: [(new tokens, context length after the step)]."""
    rng = np.random.default_rng(seed)
    s = len(rows) + spare                  # padding slots
    t = sum(r[0] for r in rows) + 3        # padding rows at the end
    cu = np.zeros(s + 1, np.int32)
    ctx = np.zeros(s, np.int32)
    for i, (nq, c) in enumerate(rows):
        cu[i + 1], ctx[i] = cu[i] + nq, c
    cu[len(rows) + 1:] = cu[len(rows)]
    slots = rng.permutation(s)[:s].astype(np.int32)   # any order
    slots[len(rows):] = s                              # scratch
    return dict(
        x=rng.standard_normal((t, e)).astype(np.float32),
        dt=np.abs(rng.standard_normal((t, e))).astype(np.float32) * 0.3,
        a=-np.exp(rng.standard_normal((e, n))).astype(np.float32),
        b=rng.standard_normal((t, n)).astype(np.float32),
        c=rng.standard_normal((t, n)).astype(np.float32),
        state=rng.standard_normal((s + 1, n, e)).astype(np.float32),
        conv_state=rng.standard_normal((s + 1, 3, e)).astype(np.float32),
        w=rng.standard_normal((4, e)).astype(np.float32),
        bias=rng.standard_normal(e).astype(np.float32),
        slots=slots, cu=cu, ctx=ctx, ns=np.int32(len(rows)))


SCAN_ROWS = {
    "decode_only": [(1, 9), (1, 2), (1, 30)],
    "fresh_prefill": [(11, 11)],
    "mixed": [(1, 9), (10, 10), (9, 25), (1, 1), (17, 40)],
    # what the kernel's walk has to get right: a chunk that crosses its
    # 128-token tiles beside one-token rows, a row with no token, a row
    # from position 0 over a slot that holds another request's state
    "decodes_beside_a_long_chunk": [(1, 5), (1, 1), (150, 190), (1, 7)],
    "a_row_with_no_token": [(1, 9), (0, 5), (6, 6), (1, 3)],
    "from_zero_over_a_stale_slot": [(7, 7), (1, 1), (3, 9)],
}
SCAN_IMPLS = ("xla", "interpret")


def _scan_args(k):
    return [jnp.asarray(k[n]) for n in ("x", "dt", "a", "b", "c", "state",
                                        "slots", "cu", "ctx")]


@pytest.mark.parametrize("impl", SCAN_IMPLS)
@pytest.mark.parametrize("name", sorted(SCAN_ROWS))
def test_ragged_scan_matches_per_token_loop(name, impl):
    k = _scan_case(1, SCAN_ROWS[name])
    y, st = ragged_selective_scan(*_scan_args(k), k["ns"], impl=impl)
    y, st = np.asarray(y), np.asarray(st)
    want_st = k["state"].copy()
    for i in range(int(k["ns"])):
        lo, hi = k["cu"][i], k["cu"][i + 1]
        fresh = k["ctx"][i] - (hi - lo) == 0
        h = (np.zeros_like(want_st[0]) if fresh
             else k["state"][k["slots"][i]]).astype(np.float64)
        for t in range(lo, hi):
            h = (np.exp(k["dt"][t][None, :] * k["a"].T) * h
                 + (k["dt"][t] * k["x"][t])[None, :] * k["b"][t][:, None])
            np.testing.assert_allclose(
                y[t], (h * k["c"][t][:, None]).sum(0), rtol=2e-5, atol=2e-5)
        want_st[k["slots"][i]] = h
    live = [int(s) for s in k["slots"][:int(k["ns"])]]
    np.testing.assert_allclose(st[live], want_st[live], rtol=2e-5,
                               atol=2e-5)
    # no slot but the live rows' and the scratch one is touched
    rest = [s for s in range(st.shape[0] - 1) if s not in live]
    np.testing.assert_array_equal(st[rest], k["state"][rest])
    assert np.isfinite(y).all()


@pytest.mark.parametrize("name,shape", [
    ("decode_only", dict(spare=4)),             # num_seqs < slots
    ("mixed", dict()),                          # T = 41: no tile's multiple
    ("mixed", dict(e=256, n=8, spare=2)),       # two lane tiles of 128
    ("decodes_beside_a_long_chunk", dict()),    # T = 156: two token tiles
    ("a_row_with_no_token", dict(spare=3)),
    ("from_zero_over_a_stale_slot", dict()),
], ids=["decode_only", "mixed", "mixed_e256", "long_chunk", "no_token",
        "stale_slot"])
def test_scan_kernel_equals_the_xla_route(name, shape):
    """The Pallas kernel through the interpreter against ``_scan_xla``,
    its oracle: equal to float32 rounding on the live rows and slots, and
    every other slot bit for bit what it was."""
    k = _scan_case(5, SCAN_ROWS[name], **shape)
    want_y, want_st = ragged_selective_scan(*_scan_args(k), k["ns"],
                                            impl="xla")
    y, st = ragged_selective_scan(*_scan_args(k), k["ns"],
                                  impl="interpret")
    tokens = int(k["cu"][int(k["ns"])])
    np.testing.assert_allclose(np.asarray(y)[:tokens],
                               np.asarray(want_y)[:tokens], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(y)[tokens:], 0.0)
    rows = [i for i, r in enumerate(SCAN_ROWS[name]) if r[0]]
    live = [int(k["slots"][i]) for i in rows]
    np.testing.assert_allclose(np.asarray(st)[live],
                               np.asarray(want_st)[live], rtol=1e-6,
                               atol=1e-6)
    rest = [s for s in range(st.shape[0]) if s not in live]
    np.testing.assert_array_equal(np.asarray(st)[rest], k["state"][rest])


@pytest.mark.parametrize("impl", SCAN_IMPLS)
def test_scan_row_does_not_depend_on_its_neighbours(impl):
    """A row's y and state are bitwise the same alone in the stream and
    beside other rows, wherever in the stream (and in which of the
    kernel's token tiles and state buffers) it stands."""
    rows = SCAN_ROWS["decodes_beside_a_long_chunk"] + [(9, 20)]
    k = _scan_case(6, rows)
    y, st = ragged_selective_scan(*_scan_args(k), k["ns"], impl=impl)
    for i, (nq, ctx) in enumerate(rows):
        lo = int(k["cu"][i])
        alone = dict(k, cu=np.array([0, nq] + [nq] * (len(rows) - 1),
                                    np.int32),
                     ctx=np.array([ctx] + [0] * (len(rows) - 1), np.int32),
                     slots=np.roll(k["slots"], -i), ns=np.int32(1))
        for name in ("x", "dt", "b", "c"):
            alone[name] = np.roll(k[name], -lo, axis=0)
        y1, st1 = ragged_selective_scan(*_scan_args(alone), alone["ns"],
                                        impl=impl)
        np.testing.assert_array_equal(np.asarray(y1)[:nq],
                                      np.asarray(y)[lo:lo + nq])
        slot = int(k["slots"][i])
        np.testing.assert_array_equal(np.asarray(st1)[slot],
                                      np.asarray(st)[slot])


def test_scan_step_is_the_decode_form():
    k = _scan_case(2, [(1, 4)] * 3)
    h = jnp.asarray(k["state"][:3])
    h2, y = selective_scan_step(h, jnp.asarray(k["x"][:3]),
                                jnp.asarray(k["dt"][:3]),
                                jnp.asarray(k["a"].T),
                                jnp.asarray(k["b"][:3]),
                                jnp.asarray(k["c"][:3]))
    for i in range(3):
        hi, yi = selective_scan_step(h[i], k["x"][i], k["dt"][i],
                                     jnp.asarray(k["a"].T), k["b"][i],
                                     k["c"][i])
        np.testing.assert_allclose(h2[i], hi, rtol=1e-6)
        np.testing.assert_allclose(y[i], yi, rtol=1e-6)


def test_scan_refuses_an_unknown_impl_by_name():
    k = _scan_case(3, [(1, 4)])
    with pytest.raises(ValueError, match="unknown selective scan impl"):
        ragged_selective_scan(*_scan_args(k), k["ns"], impl="cuda")


@pytest.mark.parametrize("name", sorted(SCAN_ROWS))
def test_ragged_conv_matches_per_token_loop(name):
    k = _scan_case(4, SCAN_ROWS[name])
    out, st = ragged_causal_conv(
        jnp.asarray(k["x"]), jnp.asarray(k["w"]), jnp.asarray(k["bias"]),
        jnp.asarray(k["conv_state"]), jnp.asarray(k["slots"]),
        jnp.asarray(k["cu"]), jnp.asarray(k["ctx"]), k["ns"])
    out, st = np.asarray(out), np.asarray(st)
    for i in range(int(k["ns"])):
        lo, hi = k["cu"][i], k["cu"][i + 1]
        fresh = k["ctx"][i] - (hi - lo) == 0
        hist = (np.zeros((3, k["x"].shape[1]), np.float32) if fresh
                else k["conv_state"][k["slots"][i]])
        seq = np.concatenate([hist, k["x"][lo:hi]])
        for j, t in enumerate(range(lo, hi)):
            want = (seq[j:j + 4] * k["w"]).sum(0) + k["bias"]
            np.testing.assert_allclose(out[t], want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(st[k["slots"][i]], seq[-3:], rtol=1e-6)


# -- (g) the padded-query route -------------------------------------------
@pytest.mark.parametrize("folded", [False, True])
@pytest.mark.parametrize("window", [None, 5])
def test_padded_query_route_is_differential_attention(window, folded):
    rng = np.random.default_rng(7)
    t, h, kh, d, bs = 19, 8, 4, 16, 4
    q = rng.standard_normal((t, h, d)).astype(np.float32)
    k = rng.standard_normal((t, kh, d)).astype(np.float32)
    v = rng.standard_normal((t, kh, d)).astype(np.float32)
    lam, lam_init, eps = 0.37, 0.55, 1e-5
    mb = -(-t // bs)
    pool = (mb, bs, kh * d) if folded else (mb, bs, kh // 2, 2 * d)
    out, _, _ = ragged_paged_attention(
        phi4flash._padded_queries(jnp.asarray(q)),
        jnp.asarray(k).reshape(t, kh // 2, 2 * d),
        jnp.asarray(v).reshape(t, kh // 2, 2 * d),
        jnp.zeros(pool), jnp.zeros(pool),
        jnp.arange(mb, dtype=jnp.int32)[None], jnp.asarray([0, t]),
        jnp.asarray([t]), jnp.int32(1), scale=1.0 / math.sqrt(d),
        impl="ref", window=window)
    got = phi4flash._diff_combine(out, lam, lam_init, eps)
    want = ref.diff_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), lam, lam_init, window, eps)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _kernel_case(seed, rows, h=4, kh=2, d=32, bs=4, mb=12):
    rng = np.random.default_rng(seed)
    s = len(rows) + 1
    t = 32
    cu = np.zeros(s + 1, np.int32)
    ctx = np.zeros(s, np.int32)
    for i, (nq, c) in enumerate(rows):
        cu[i + 1], ctx[i] = cu[i] + nq, c
    cu[len(rows) + 1:] = cu[len(rows)]
    bt = rng.permutation(s * mb).reshape(s, mb).astype(np.int32)
    f = jnp.float32
    return dict(
        q=jnp.asarray(rng.standard_normal((t, h, d)), f),
        k=jnp.asarray(rng.standard_normal((t, kh, d)), f),
        v=jnp.asarray(rng.standard_normal((t, kh, d)), f),
        kc=jnp.asarray(rng.standard_normal((s * mb, bs, kh, d)), f),
        vc=jnp.asarray(rng.standard_normal((s * mb, bs, kh, d)), f),
        bt=bt, cu=jnp.asarray(cu), ctx=jnp.asarray(ctx),
        ns=jnp.int32(len(rows)))


KERNEL_ROWS = [(1, 30), (9, 20), (1, 3), (14, 45)]


@pytest.mark.parametrize("folded", [False, True])
@pytest.mark.parametrize("window", [None, 8, 13])
@pytest.mark.parametrize("read_only", [False, True])
def test_kernel_window_and_read_only_match_the_oracle(window, read_only,
                                                      folded):
    """The Pallas kernel, interpreted, against the jnp oracle: a window
    bound (with the block-table entries behind it gone), the read-only
    call that writes nothing, and the folded cache (a token's heads side
    by side on lanes) against the 4-D one."""
    k = _kernel_case(11, KERNEL_ROWS)
    plain = None
    if folded:
        plain = ragged_paged_attention(
            k["q"], *((None, None) if read_only else (k["k"], k["v"])),
            k["kc"], k["vc"], jnp.asarray(k["bt"]), k["cu"], k["ctx"],
            k["ns"], impl="ref", window=window)[0]
        k["kc"] = k["kc"].reshape(*k["kc"].shape[:2], -1)
        k["vc"] = k["vc"].reshape(*k["vc"].shape[:2], -1)
    bt = k["bt"].copy()
    if window is not None:          # what release would have handed back
        for i, (nq, c) in enumerate(KERNEL_ROWS):
            bt[i, :max(c - nq - window, 0) // 4] = -1
    new = (None, None) if read_only else (k["k"], k["v"])
    outs = [ragged_paged_attention(
        k["q"], *new, k["kc"], k["vc"], jnp.asarray(bt), k["cu"],
        k["ctx"], k["ns"], impl=impl, window=window)
        for impl in ("ref", "interpret")]
    n_valid = int(k["cu"][-1])
    np.testing.assert_allclose(outs[0][0][:n_valid], outs[1][0][:n_valid],
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(outs[0][1:], outs[1][1:]):
        np.testing.assert_array_equal(a, b)
    if read_only:
        np.testing.assert_array_equal(outs[1][1], k["kc"])
        np.testing.assert_array_equal(outs[1][2], k["vc"])
    if plain is not None and window is None:
        # (with a window the released entries differ between the two)
        np.testing.assert_allclose(outs[0][0][:n_valid], plain[:n_valid],
                                   rtol=2e-5, atol=2e-5)


def test_window_walk_reads_no_page_behind_the_window():
    """With every page behind the window poisoned, the windowed result
    does not change: the walk starts at the first live page."""
    k = _kernel_case(12, KERNEL_ROWS)
    kc = np.asarray(k["kc"]).copy()
    for i, (nq, c) in enumerate(KERNEL_ROWS):
        for j in range(max(c - nq - 8, 0) // 4):
            kc[k["bt"][i, j]] = np.nan
    for impl in ("ref", "interpret"):
        clean, _, _ = ragged_paged_attention(
            k["q"], None, None, k["kc"], k["vc"], jnp.asarray(k["bt"]),
            k["cu"], k["ctx"], k["ns"], impl=impl, window=8)
        dirty, _, _ = ragged_paged_attention(
            k["q"], None, None, jnp.asarray(kc), k["vc"],
            jnp.asarray(k["bt"]), k["cu"], k["ctx"], k["ns"], impl=impl,
            window=8)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


# -- (b) engine prefill + decode ------------------------------------------
@pytest.mark.parametrize("lengths", [(5, 3), (21, 30), (5, 30, 17, 9, 26)],
                         ids=["under_window", "over_window", "mixed"])
def test_engine_logits_match_reference(model, lengths):
    prompts = prompts_of(lengths)
    gen, spy, eng = serve(model, prompts, 12)
    assert assert_against_reference(model, prompts, gen, spy) == \
        12 * len(lengths)
    assert eng.num_logits_fetches == 0
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 0 and snap["kv_blocks_window"] == 0
    assert snap["kv_blocks_full"] == 0


# -- (c) chunk-to-chunk state ---------------------------------------------
def test_three_chunks_equal_one(model):
    prompts = prompts_of([40], seed=9)
    one, spy1, _ = serve(model, prompts, 6, max_batched_tokens=64)
    three, spy3, eng3 = serve(model, prompts, 6, max_batched_tokens=16)
    assert eng3.scheduler.num_prefill_chunks == 3
    assert one == three
    for c in range(40, 46):
        np.testing.assert_allclose(spy3.logits[("r0", c)],
                                   spy1.logits[("r0", c)], rtol=1e-4,
                                   atol=1e-4)
    assert_against_reference(model, prompts, three, spy3)


# -- (d) preemption by recompute ------------------------------------------
def test_preempted_request_continues_with_the_same_logits(model):
    prompts = prompts_of([22, 13], seed=10)
    plain, _, _ = serve(model, prompts, 10)
    state = {"armed": False}

    def preempt_once(eng):
        r1 = eng.get_request("r1") if "r1" in eng._requests else None
        if not state["armed"] and r1 is not None and r1.num_generated == 4:
            faults.install("serving.force_oom.r1:flag*1")
            state["armed"] = True

    try:
        gen, spy, eng = serve(model, prompts, 10, check=preempt_once)
    finally:
        faults.clear()
    assert eng.scheduler.num_preemptions >= 1
    assert gen == plain
    assert_against_reference(model, prompts, gen, spy)


# -- (e) window release ---------------------------------------------------
def test_window_release_changes_no_logit_and_bounds_the_pool(model):
    prompts = prompts_of([30, 6], seed=11)
    bound = -(-(WINDOW + 16) // BS) + 2
    peak = {"live": 0}

    def watch(eng):
        for r in eng.scheduler.running:
            live = eng.block_manager.num_window_blocks(r.request_id)
            peak["live"] = max(peak["live"], live)
            assert live <= bound, (r.request_id, r.num_cached, live)

    gen, spy, eng = serve(model, prompts, 40, check=watch)
    assert eng.block_manager.num_window_blocks_released > 0
    assert eng.metrics.snapshot()["window_blocks_released"] == \
        eng.block_manager.num_window_blocks_released
    assert peak["live"] >= WINDOW // BS      # a whole window was held
    assert_against_reference(model, prompts, gen, spy)

    import paddle_tpu.serving.block_manager as bm_mod

    real = bm_mod.BlockManager.release_behind_window
    bm_mod.BlockManager.release_behind_window = lambda *a: 0
    try:
        kept, spy_kept, eng_kept = serve(model, prompts, 40)
    finally:
        bm_mod.BlockManager.release_behind_window = real
    assert eng_kept.block_manager.num_window_blocks_released == 0
    assert kept == gen
    for key, lg in spy.logits.items():
        np.testing.assert_allclose(lg, spy_kept.logits[key], rtol=1e-5,
                                   atol=1e-5)


def test_window_pool_smaller_than_the_sequences_still_serves(model):
    """A window pool far below max_num_seqs x max_model_len serves
    sequences of 70 tokens: only release makes that fit."""
    prompts = prompts_of([30, 28, 12], seed=12)
    gen, spy, eng = serve(model, prompts, 40, num_window_blocks=36)
    assert eng.cfg.num_window_blocks * BS < 3 * 70
    assert_against_reference(model, prompts, gen, spy)


# -- (f) the cross-decoder on S rows --------------------------------------
def test_cross_decoder_on_gathered_rows_equals_all_rows(model):
    prompts = prompts_of([19, 7, 26], seed=13)
    gen, spy, _ = serve(model, prompts, 5)
    model.config.cross_decoder_rows = "all"
    try:
        gen_all, spy_all, _ = serve(model, prompts, 5)
    finally:
        model.config.cross_decoder_rows = "last"
    assert gen == gen_all
    assert spy.logits.keys() == spy_all.logits.keys()
    for key, lg in spy.logits.items():
        np.testing.assert_allclose(lg, spy_all.logits[key], rtol=1e-4,
                                   atol=1e-4)


# -- (h) rows move, state stays -------------------------------------------
def test_requests_swapping_rows_keep_their_own_state(model):
    prompts = prompts_of([6, 14, 9], seed=14)
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                        max_model_len=96,
                                        max_batched_tokens=16))
    spy = LogitSpy(eng)
    want_new = {"r0": 3, "r1": 14, "r2": 14}
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(
            max_new_tokens=want_new[rid]))
    gen = {}
    late = prompts_of([11], seed=15)["r0"]
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished:
                gen[out.request_id] = list(out.generated)
                eng.release_request(out.request_id)
                if out.request_id == "r0":      # its row and slot free up
                    eng.add_request("late", late,
                                    SamplingParams(max_new_tokens=6))
    prompts["late"] = late
    assert_against_reference(model, prompts, gen, spy)
    for rid in ("r1", "r2"):
        rows = {i for i, _ in spy.rows[rid]}
        slots = {s for _, s in spy.rows[rid]}
        assert len(rows) > 1, f"{rid} never changed rows"
        assert len(slots) == 1, f"{rid} changed state slots"


# -- (i) refusals by name -------------------------------------------------
@pytest.mark.parametrize("knobs,name", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(kv_tiers=True), "kv_tiers"),
    (dict(swap_mode="host"), "swap_mode='host'"),
    (dict(tp_degree=2), "tp_degree > 1"),
    (dict(draft_model=object(), num_spec_tokens=2), "draft_model"),
])
def test_engine_refuses_by_name_at_construction(model, knobs, name):
    with pytest.raises(ValueError) as e:
        LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                      max_model_len=32, **knobs))
    assert name in str(e.value) and "cache_spec" in str(e.value)


@pytest.mark.parametrize("call,name", [
    (lambda e: e.export_kv("r0"), "export_kv"),
    (lambda e: e.import_kv("r9", [1, 2], meta={}, payload=b""),
     "import_kv"),
    (lambda e: e.export_prefix("00"), "export_prefix"),
    (lambda e: e.import_prefix(meta={}, payload=b""), "import_prefix"),
    (lambda e: e.park_session("s"), "sessions"),
    (lambda e: e.resume_session("r9", "s", [1, 2]), "sessions"),
])
def test_engine_refuses_kv_shipping_and_sessions_by_name(model, call, name):
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                        max_model_len=32))
    assert eng.cfg.prefix_cache is False      # the default resolves to off
    with pytest.raises(ValueError) as e:
        call(eng)
    assert name in str(e.value) and "cache_spec" in str(e.value)


def test_state_slots_refuse_past_max_num_seqs():
    from paddle_tpu.serving.block_manager import (
        BlockManager, NoFreeBlocksError,
    )

    bm = BlockManager(64, BS, window_blocks=32, window=WINDOW,
                      state_slots=2)
    bm.allocate("a", 5)
    bm.allocate("b", 9)
    assert bm.state_slots_in_use == 2 and not bm.can_allocate(1)
    with pytest.raises(NoFreeBlocksError, match="state slots"):
        bm.allocate("c", 3)
    assert not bm.has_table("c")
    bm.free("a")
    assert bm.state_slots_in_use == 1
    bm.allocate("c", 3)
    assert {bm.state_slot("b"), bm.state_slot("c")} == {0, 1}
    bm.check_invariants()


def test_window_pool_accounting_through_growth_and_release():
    from paddle_tpu.serving.block_manager import (
        BlockManager, NoFreeBlocksError,
    )

    bm = BlockManager(64, BS, window_blocks=6, window=WINDOW,
                      state_slots=2)
    bm.allocate("a", 10)                       # 3 blocks of each pool
    assert bm.num_used_window_blocks == 3 == bm.num_used_blocks
    assert bm.can_append("a", 24) and not bm.can_append("a", 25)
    with pytest.raises(NoFreeBlocksError, match="window-pool"):
        bm.append_slot("a", 40)
    assert len(bm.block_table("a")) == 3       # took from neither pool
    bm.append_slot("a", 24)
    assert bm.release_behind_window("a", 24) == 4   # (24 - 8) // 4
    assert bm.window_table("a")[:4] == [-1] * 4
    assert bm.num_window_blocks("a") == 2 and bm.num_used_blocks == 6
    bm.append_slot("a", 40)                    # fits now
    bm.check_invariants()
    bm.free("a")
    assert bm.num_used_window_blocks == 0 == bm.num_used_blocks
    bm.check_invariants()


def test_a_model_without_cache_spec_gets_the_per_layer_pair():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    eng = LLMEngine(m, EngineConfig(block_size=BS, max_num_seqs=2,
                                    max_model_len=32))
    assert eng._cache is None
    for caches in (eng._kcs, eng._vcs):
        assert len(caches) == m.config.num_hidden_layers
        assert all(c.ndim == 4 for c in caches)
    assert eng.block_manager.window_blocks == 0
    assert eng.block_manager.state_slots == 0
    assert eng.cfg.prefix_cache is True
    out = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
    assert len(out[0]) == 3
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 0 == snap["kv_blocks_window"]


def test_dispatch_and_post_spans_carry_the_new_counters(model):
    from paddle_tpu import profiler

    prof = profiler.Profiler(record_op_events=False).start()
    try:
        serve(model, prompts_of([30, 6], seed=16), 20)
    finally:
        prof.stop()
    disp = [e for e in prof.host_events if e["name"] == "engine.dispatch"]
    post = [e for e in prof.host_events if e["name"] == "engine.post"]
    for key in ("state_rows", "first_rows", "win_blocks", "full_blocks",
                "cross_rows"):
        assert all(key in e["args"] for e in disp), key
    assert sum(e["args"]["first_rows"] for e in disp) == 2
    assert any(e["args"]["state_rows"] == 2 for e in disp)
    assert all(e["args"]["cross_rows"] == e["args"]["rows"] for e in disp)
    assert sum(e["args"]["window_blocks_released"] for e in post) > 0
