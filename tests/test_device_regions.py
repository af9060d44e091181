"""Device regions (``paddle_tpu/profiler/regions.py``): the closed
vocabulary of ``jax.named_scope`` names, the map from a compiled step's
text to ``{HLO instruction: region}``, and the way a reader gets that map
after the run. CPU, tiny widths: names, counts and lifetimes; no time
here means anything."""
import ast
import contextlib
import gc
import glob
import os
import re
import time
import weakref

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer, profiler
from paddle_tpu.profiler import (DEVICE_REGIONS, Profiler, ProfilerTarget,
                                 program_regions, region_map, regions)
from paddle_tpu.serving import EngineConfig, LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRIVIAL = regions._TRIVIAL   # parameter, tuple, get-tuple-element, ...


# -- the vocabulary ------------------------------------------------------------
def _named_scope_literals():
    found = {}
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope" and node.args):
                # a conditional expression names two regions
                for leaf in ast.walk(node.args[0]):
                    if isinstance(leaf, ast.Constant) and isinstance(
                            leaf.value, str):
                        found.setdefault(leaf.value, []).append(
                            os.path.relpath(path, ROOT))
    return found


def test_vocabulary_equals_the_named_scope_literals():
    found = _named_scope_literals()
    assert set(found) == set(DEVICE_REGIONS), (
        sorted(set(found) ^ set(DEVICE_REGIONS)))
    assert len(DEVICE_REGIONS) == len(set(DEVICE_REGIONS))


# -- region_map on canned text ---------------------------------------------------
def _module(entry_body, more=""):
    head = '  %head = f32[8]{0} dot(%p, %p), metadata={op_name="jit(s)/' \
           'lm_head/dot_general"}\n'
    return (f"HloModule jit_s\n\n{more}\nENTRY %main.1 (p: f32[8]) -> "
            f"f32[8] {{\n  %p = f32[8]{{0}} parameter(0)\n{head}"
            f"{entry_body}}}\n")


FUSED_DOT = '''%fused_computation.1 (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %dot.5 = f32[8,8]{1,0} dot(%a, %b), metadata={op_name="jit(s)/transpose(jvp(mlp))/dot_general"}
  ROOT %add.9 = f32[8,8]{1,0} add(%dot.5, %b), metadata={op_name="jit(s)/optimizer/add"}
}
'''
FUSED_LOOP = '''%fused_computation.2 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(s)/sampler/mul"}
  ROOT %exp.1 = f32[8]{0} exponential(%mul.1), metadata={op_name="jit(s)/sampler/exp"}
}
'''
LOOP = '''%body.1 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %copy.77 = f32[8]{0} copy(%x)
  %sort.3 = f32[8]{0} sort(%copy.77), dimensions={0}, to_apply=%cmp.1, metadata={op_name="jit(s)/index_select/while/body/sort"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%i, %sort.3)
}

%cond.1 (t: (s32[], f32[8])) -> pred[] {
  %t = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%i, %i), direction=LT, metadata={op_name="jit(s)/index_select/while/cond/lt"}
}
'''
CASES = {
    "plain_op": (
        '  %dot.1 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(s)/'
        'jit(main)/mlp/dot_general" source_file="m.py"}\n', "",
        {"dot.1": ("mlp", ("mlp",), False, ())}),
    "transpose_jvp": (
        '  %dot.2 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(s)/'
        'transpose(jvp(attn_proj))/dot_general"}\n', "",
        {"dot.2": ("attn_proj", ("attn_proj",), True, ())}),
    "nested_chain": (
        '  %g.1 = f32[8]{0} gather(%p, %p), metadata={op_name="jit(s)/'
        'jit(_layer)/sparse_attention/attention/jit(_where)/gather"}\n', "",
        {"g.1": ("attention", ("sparse_attention", "attention"), False,
                 ())}),
    "fusion_filed_by_its_hero": (
        '  %fusion.1 = f32[8,8]{1,0} fusion(%p, %p), kind=kOutput, '
        'calls=%fused_computation.1, metadata={op_name="jit(s)/optimizer/'
        'add"}\n', FUSED_DOT,
        {"fusion.1": ("mlp", ("mlp",), True, ("optimizer",))}),
    "fusion_filed_by_its_root": (
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%fused_computation.2\n', FUSED_LOOP,
        {"fusion.2": ("sampler", ("sampler",), False, ())}),
    "while_and_its_body": (
        '  %while.1 = (s32[], f32[8]{0}) while(%p), condition=%cond.1, '
        'body=%body.1, metadata={op_name="jit(s)/index_select/while"}\n',
        LOOP,
        {"while.1": ("index_select", ("index_select",), False, ()),
         "sort.3": ("index_select", ("index_select",), False, ()),
         # the compiler's own copy, no op_name: part of the loop
         "copy.77": ("index_select", ("index_select",), False, ()),
         "lt.1": ("index_select", ("index_select",), False, ())}),
    "mosaic_custom_call": (
        '  %custom-call.7 = f32[8]{0} custom-call(%p), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/'
        'latent_attention/attention/jit(_ragged_attend_pallas)/'
        'ragged_paged_attention"}\n', "",
        {"custom-call.7": ("attention", ("latent_attention", "attention"),
                           False, ())}),
    "unscoped_op": (
        '  %add.4 = f32[8]{0} add(%p, %p), metadata={op_name="jit(s)/'
        'jit(clip)/add"}\n  %copy.5 = f32[8]{0} copy(%p)\n', "",
        {"add.4": (None, (), False, ()), "copy.5": (None, (), False, ())}),
    "prefetch_goes_with_its_reader": (
        '  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%p)\n'
        '  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)\n'
        '  %dot.8 = f32[8]{0} dot(%copy-done.1, %p), metadata={op_name='
        '"jit(s)/moe_shared/dot_general"}\n', "",
        {"copy-start.1": ("moe_shared", ("moe_shared",), False, ()),
         "copy-done.1": ("moe_shared", ("moe_shared",), False, ())}),
    "result_on_its_way_out_goes_with_its_writer": (
        '  %dot.9 = f32[8]{0} dot(%p, %p), metadata={op_name='
        '"jit(s)/optimizer/mul"}\n'
        '  %copy.9 = f32[8]{0} copy(%dot.9)\n'
        '  ROOT %tuple.9 = (f32[8]{0}) tuple(%copy.9)\n', "",
        {"copy.9": ("optimizer", ("optimizer",), False, ())}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_region_map_on_canned_text(case):
    body, more, want = CASES[case]
    got = region_map(_module(body, more))
    assert got["head"]["region"] == "lm_head"
    for name, (region, chain, backward, mixed) in want.items():
        at = got[name]
        assert (at["region"], at["chain"], at["backward"], at["mixed"]) == (
            region, chain, backward, mixed), (name, at)
    # a fused computation's members are not ops of the device's line
    assert "dot.5" not in got and "mul.1" not in got


def test_a_text_with_none_of_the_covering_regions_yields_nothing():
    """A stale executable (a persistent cache keyed without metadata may
    hand back the one compiled before the regions covered the step)."""
    stale = _module(CASES["while_and_its_body"][0],
                    CASES["while_and_its_body"][1]).replace(
                        "lm_head/dot_general", "lm_head_loss/dot_general")
    assert "index_select" in stale and region_map(stale) == {}
    assert region_map("") == {} and region_map("not hlo") == {}


def test_self_times_nest_by_containment():
    ev = [("while.1", 0, 100), ("a", 10, 30), ("b", 30, 50), ("a", 60, 90),
          ("c", 100, 120), ("d", 130, 140)]
    got = sorted(profiler.self_times(ev))
    assert got == sorted([("while.1", 100, 30), ("a", 20, 20), ("b", 20, 20),
                          ("a", 30, 30), ("c", 20, 20), ("d", 10, 10)])
    assert sum(own for _, _, own in got) == 130     # the busy union


# -- the tiny steps of the four block designs and the train step ---------------
def _llama(**kw):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


def _hybrid():
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)

    return Phi4FlashForCausalLM(Phi4FlashConfig.tiny())


def _moe():
    from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM

    return MlaMoeForCausalLM(MlaMoeConfig.tiny())


def _sparse():
    from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM

    return Dots3ForCausalLM(Dots3Config.tiny(experts_held=(2, 4)))


def _engine(model, block_size=4):
    model.eval()
    return LLMEngine(model, EngineConfig(
        block_size=block_size, max_num_seqs=4, max_model_len=96,
        max_batched_tokens=16))


def _serve_step(build, block_size=4):
    """(the engine's jitted step, the arguments of one real dispatch)."""
    paddle.seed(0)
    eng = _engine(build(), block_size)
    seen, real = [], eng._jstep_ragged

    def spy(*args):
        seen.append(args)
        return real(*args)

    eng._jstep_ragged = spy
    eng.add_request("a", [1, 2, 3, 4, 5])
    eng.step()
    return real, seen[0]


def _train_step():
    from paddle_tpu.models.llama import LlamaPretrainingCriterion

    paddle.seed(0)
    model = _llama(use_flash_attention="interpret")
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), opt)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    return step._jitted, (
        1, step._carry, [p._data for p in step._params], step._slots,
        [b._data for b in step._buffers], jnp.float32(1e-3),
        step._scaler_state, tokens, tokens)


STEPS = {"llama": lambda: _serve_step(_llama),
         "hybrid": lambda: _serve_step(_hybrid),
         "latent_moe": lambda: _serve_step(_moe, block_size=8),
         "sparse": lambda: _serve_step(_sparse),
         "train": _train_step}
HAS = {"llama": {"embed", "attn_proj", "attention", "kv_update", "mlp",
                 "lm_head", "sampler"},
       "hybrid": {"embed", "attn_proj", "ssm_proj", "ssm_scan", "ssm_conv",
                  "gmu", "mlp", "lm_head", "sampler", "cross_attention",
                  "window_attention", "full_attention"},
       "latent_moe": {"embed", "attn_proj", "mla_absorb", "mlp",
                      "moe_router", "moe_dispatch", "moe_experts",
                      "moe_shared", "lm_head", "sampler"},
       "sparse": {"embed", "attn_proj", "mla_absorb", "attn_gate",
                  "index_proj", "index_scores", "index_select", "mlp",
                  "moe_router", "moe_shared", "lm_head", "sampler"},
       "train": {"embed", "attn_proj", "attention", "mlp", "lm_head",
                 "lm_head_loss", "optimizer"}}


@pytest.mark.parametrize("design", sorted(STEPS))
def test_at_most_a_twentieth_of_a_step_is_outside_every_region(design):
    jitted, args = STEPS[design]()
    # as program_regions() reads it: past a stale entry of the suite's
    # persistent cache (another checkout's executable, older scopes)
    placed = regions._compiled_regions(jitted.lower(*args))
    real = {k: v for k, v in placed.items() if v["opcode"] not in TRIVIAL}
    outside = sorted(k for k, v in real.items() if v["region"] is None)
    print(f"{design}: {len(outside)} of {len(real)} instructions under no "
          f"region: {outside}")
    assert real and len(outside) <= 0.05 * len(real), outside
    assert HAS[design] <= {v["region"] for v in real.values()}
    if design == "train":
        backward = {v["region"] for v in real.values() if v["backward"]}
        assert {"attn_proj", "mlp", "lm_head"} <= backward
        assert "optimizer" not in backward


def _stripped(lowered):
    """The lowered text without its locations: the ``#loc`` table and
    every ``loc(...)``, nested parentheses and all."""
    text = re.sub(r"^#loc.*\n", "", lowered.as_text(debug_info=True),
                  flags=re.M)
    out, at = [], 0
    for m in re.finditer(r"\s*\bloc\(", text):
        if m.start() < at:
            continue
        depth, end = 1, m.end()
        while depth:
            depth += {"(": 1, ")": -1}.get(text[end], 0)
            end += 1
        out.append(text[at:m.start()])
        at = end
    return "".join(out) + text[at:]


@pytest.mark.parametrize("design", sorted(STEPS))
def test_scopes_change_no_instruction_of_a_step(design, monkeypatch):
    """The lowered text, locations stripped, is byte-identical with the
    scopes and with every ``jax.named_scope`` made a no-op."""
    jitted, args = STEPS[design]()
    with_scopes = _stripped(jitted.lower(*args))
    assert "lm_head" in jitted.lower(*args).as_text(debug_info=True)
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jitted, args = STEPS[design]()
    bare = jitted.lower(*args)
    assert "lm_head" not in bare.as_text(debug_info=True)
    assert _stripped(bare) == with_scopes
    jax.clear_caches()


# -- getting the map after the run ---------------------------------------------
@pytest.fixture
def fresh_registry():
    with regions._lock:
        regions._programs.clear()
    yield
    with regions._lock:
        regions._programs.clear()


def _serve_a_little(eng, n=3):
    eng.add_request(f"r{time.monotonic_ns()}", [1, 2, 3, 4, 5])
    for _ in range(n):
        eng.step()


def test_no_session_nothing_is_pinned_and_a_dropped_engine_is_freed(
        fresh_registry):
    paddle.seed(0)
    eng = _engine(_llama())
    _serve_a_little(eng)
    assert not eng._step_program.pinned
    assert not jax.profiler.TraceAnnotation.is_enabled()
    dead = weakref.ref(eng)
    del eng
    gc.collect()
    assert dead() is None
    assert program_regions() == {}      # nothing left to lower


def test_the_pin_is_taken_under_a_session_and_released_on_read(
        fresh_registry, tmp_path):
    paddle.seed(0)
    eng = _engine(_llama())
    _serve_a_little(eng)                # cold dispatch, no session
    assert not eng._step_program.pinned
    prof = Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
                    trace_dir=str(tmp_path), record_op_events=False)
    prof.start()
    _serve_a_little(eng)
    prof.stop()
    program = eng._step_program
    assert program.pinned
    dead = weakref.ref(eng)
    del eng
    gc.collect()
    assert dead() is not None           # readable after the run
    placed = program_regions()
    assert set(placed) == {"serve.step"}
    assert {"embed", "mlp", "lm_head", "sampler"} <= {
        v["region"] for v in placed["serve.step"].values()}
    assert not program.pinned
    gc.collect()
    assert dead() is None               # released on read
    assert program_regions()["serve.step"] is placed["serve.step"]


def test_a_later_session_drops_an_earlier_sessions_pins(fresh_registry,
                                                        tmp_path):
    paddle.seed(0)
    first, second = _engine(_llama()), _engine(_llama())
    for eng in (first, second):
        _serve_a_little(eng)
    for n, eng in enumerate((first, second)):
        prof = Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
                        trace_dir=str(tmp_path / str(n)),
                        record_op_events=False)
        prof.start()
        _serve_a_little(eng)
        prof.stop()
        _serve_a_little(eng)            # a dispatch sees the session end
    assert second._step_program.pinned and not first._step_program.pinned


def test_a_dispatch_with_no_session_costs_under_a_microsecond(
        fresh_registry):
    program = regions.StepProgram("serve.step", jax.jit(lambda x: x))
    owner = object()
    program.dispatched(owner)
    n = 200_000
    best = min(_timed(program.dispatched, owner, n) for _ in range(5))
    assert best / n < 1e-6, f"{best / n * 1e9:.0f} ns a dispatch"
    assert not program.pinned


def _timed(fn, arg, n):
    t0 = time.perf_counter()
    for _ in range(n):
        fn(arg)
    return time.perf_counter() - t0


def test_train_step_is_known_to_program_regions(fresh_registry):
    from paddle_tpu.models.llama import LlamaPretrainingCriterion

    paddle.seed(0)
    model = _llama()
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, LlamaPretrainingCriterion(None), opt)
    tokens = jnp.zeros((2, 16), jnp.int32)
    step(tokens, tokens)
    placed = program_regions()["train.step"]
    by_direction = {(v["region"], v["backward"]) for v in placed.values()}
    assert {("mlp", False), ("mlp", True), ("optimizer", False),
            ("lm_head_loss", False)} <= by_direction
    dead = weakref.ref(step)
    del step, model, opt
    gc.collect()
    assert dead() is None


def test_device_summary_by_region_on_a_cpu_trace(fresh_registry, tmp_path):
    paddle.seed(0)
    eng = _engine(_llama())             # a two-layer step
    assert len(eng.model.llama.layers) == 2
    _serve_a_little(eng)
    prof = Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
                    trace_dir=str(tmp_path), record_op_events=False)
    prof.start()
    _serve_a_little(eng, n=4)
    prof.stop()
    table = prof.device_summary(by="region", print_table=False)
    assert {"attn_proj", "mlp", "lm_head", "sampler"} <= set(table)
    assert all(set(row) == {"calls", "total_ms", "self_ms", "share"}
               for row in table.values())
    assert abs(sum(row["share"] for row in table.values()) - 1.0) < 1e-6
    assert all(row["self_ms"] <= row["total_ms"] + 1e-9
               for row in table.values())
    assert prof.device_summary(print_table=False)       # by op: as before
