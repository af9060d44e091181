"""tracecheck rule tests: every launch rule catches its seeded
violations (zero false negatives on the fixtures) and stays quiet on
the near-miss set (no false positives). Plus suppressions, the
baseline machinery, and the CLI contract (exit codes, --format=json)."""
import json
import textwrap

import pytest

from paddle_tpu.analysis import (
    analyze_paths, analyze_source, get_rules, load_baseline,
    write_baseline,
)
from paddle_tpu.analysis.cli import main as cli_main


def run(src):
    return analyze_source(textwrap.dedent(src), path="fixture.py")


def run_at(src, path):
    """Path-gated rules (counter-snapshot-drift is serving/fleet-scoped)
    see whatever path we claim for the fixture."""
    return analyze_source(textwrap.dedent(src), path=path)


def rules_of(findings):
    return [f.rule for f in findings]


def test_rule_catalog_has_all_launch_rules():
    names = set(get_rules())
    assert {"host-sync-in-traced", "use-after-donate",
            "trace-time-impurity", "tensor-bool-branch",
            "counter-provider-leak", "block-until-ready-in-loop",
            "unlocked-shared-state", "lock-order-cycle",
            "blocking-under-lock", "signal-handler-unsafe",
            "collective-divergence", "finish-reason-literal",
            "leaked-resource-on-raise", "counter-snapshot-drift",
            "fault-point-literal", "rpc-verb-unclassified",
            "unbounded-rpc-deadline"} <= names
    assert len(names) == 17
    for r in get_rules().values():
        assert r.summary and r.doc  # per-rule docs are part of the API


# ---------------------------------------------------------------------------
# host-sync-in-traced
# ---------------------------------------------------------------------------
class TestHostSync:
    def test_numpy_item_float_inside_jit(self):
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                a = np.asarray(x)
                b = x.numpy()
                c = float(x)
                d = x.item()
                return a, b, c, d
        """)
        assert rules_of(fs) == ["host-sync-in-traced"] * 4

    def test_reachable_through_one_helper_call(self):
        fs = run("""
            import jax

            def helper(t):
                return t.item()

            def entry(x):
                return helper(x)

            g = jax.jit(entry)
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]
        # the finding lands in helper's body, attributed to the traced
        # caller the call graph followed
        assert fs[0].line == 5
        assert "entry" in fs[0].message

    def test_partial_jit_decorator_is_traced(self):
        # @partial(jax.jit, static_argnums=...) is THE jit-with-options
        # idiom and must get the same analysis
        fs = run("""
            from functools import partial

            import jax
            import numpy as np

            @partial(jax.jit, static_argnums=(1,))
            def f(x, n):
                return np.asarray(x)

            g = jax.jit(partial(f, n=2))  # partial as wrapper arg too
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]

    def test_annotated_dispatch_result_fetch_flagged(self):
        fs = run("""
            import jax
            import numpy as np

            def go(f, x):
                step = jax.jit(f)
                out: jax.Array = step(x)
                return np.asarray(out)
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]

    def test_factory_returned_step_fn_is_traced(self):
        fs = run("""
            import jax

            def make_step(flag):
                def step_fn(x):
                    return float(x)
                return step_fn

            jitted = jax.jit(make_step(True), static_argnums=())
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]

    def test_dispatch_result_fetch_flagged(self):
        fs = run("""
            import jax
            import numpy as np

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)

                def step(self, ids):
                    logits, cache = self._jstep(ids)
                    return np.asarray(logits)
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]
        assert "compiled dispatch" in fs[0].message

    def test_near_miss_host_side_numpy_clean(self):
        fs = run("""
            import numpy as np

            def host_fn(t):
                return np.asarray(t)  # no traced scope anywhere

            def loader(batch):
                return [float(x) for x in batch]
        """)
        assert fs == []

    def test_near_miss_float_of_literal_clean(self):
        fs = run("""
            import jax

            @jax.jit
            def f(x):
                return x * float(2)  # constant, not a tensor sync
        """)
        assert fs == []

    def test_near_miss_trace_time_constants_clean(self):
        # literal lookup tables and static shape reads are host-safe
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                table = np.asarray([0.0, 1.0, 2.0])
                n = int(x.shape[0])
                d = x.ndim
                return x * table[0] * n * d
        """)
        assert fs == []

    def test_dispatch_result_method_fetch_flagged(self):
        # .item()/.numpy() ARE the headline spellings — method calls
        # have no positional args, so the receiver is the fetched value
        fs = run("""
            import jax

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)

                def step(self, ids):
                    out = self._jstep(ids)
                    return out.item(), out.numpy()
        """)
        assert rules_of(fs) == ["host-sync-in-traced"] * 2

    def test_near_miss_nested_def_binds_stay_scoped(self):
        # a closure's dispatch result must not taint the enclosing
        # function's same-named host variable
        fs = run("""
            import jax
            import numpy as np

            def outer(step_fn, data):
                out = list(data)
                step = jax.jit(step_fn)

                def inner(x):
                    out = step(x)
                    return out

                return np.asarray(out), inner
        """)
        assert fs == []

    def test_near_miss_dispatch_result_rebound_clean(self):
        # a reassigned name no longer aliases the dispatch output
        fs = run("""
            import jax
            import numpy as np

            def go(f, x):
                step = jax.jit(f)
                out = step(x)
                out = [1, 2, 3]
                return np.asarray(out)
        """)
        assert fs == []

    def test_cross_method_self_attr_fetch_flagged(self):
        # self._last parked in step(), fetched host-side in result() —
        # the None placeholder in __init__ must not clear the bind
        fs = run("""
            import jax
            import numpy as np

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)
                    self._last = None

                def step(self, ids):
                    self._last = self._jstep(ids)

                def result(self):
                    return np.asarray(self._last)
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]
        assert "self._last" in fs[0].message
        assert "step()" in fs[0].message

    def test_cross_method_self_attr_via_local_flagged(self):
        # the dispatch result routes through a local before parking on
        # self — the local's live bind must propagate to the attribute
        fs = run("""
            import jax

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)

                def step(self, ids):
                    out = self._jstep(ids)
                    self._logits = out

                def sample(self):
                    return self._logits.numpy()
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]
        assert "self._logits" in fs[0].message

    def test_near_miss_self_attr_reassigned_non_dispatch_clean(self):
        # an attribute REBOUND from host data anywhere in the class is
        # conservatively cleared: method order is unknowable statically
        fs = run("""
            import jax
            import numpy as np

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)
                    self._last = None

                def step(self, ids):
                    self._last = self._jstep(ids)

                def reset(self, ids):
                    self._last = list(ids)

                def result(self):
                    return np.asarray(self._last)
        """)
        assert fs == []

    def test_near_miss_self_attr_never_dispatch_clean(self):
        # host-only attributes fetched with numpy stay clean
        fs = run("""
            import jax
            import numpy as np

            class Eng:
                def __init__(self, f, table):
                    self._jstep = jax.jit(f)
                    self._table = table

                def lookup(self):
                    return np.asarray(self._table)
        """)
        assert fs == []

    def test_near_miss_other_class_attr_clean(self):
        # the dispatch-carrying attribute lives on Eng; a different
        # class fetching its own same-named attribute is unrelated
        fs = run("""
            import jax
            import numpy as np

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)

                def step(self, ids):
                    self._last = self._jstep(ids)

            class Logger:
                def __init__(self, rows):
                    self._last = rows

                def flush(self):
                    return np.asarray(self._last)
        """)
        assert fs == []

    def test_cross_method_tuple_elementwise_tracked(self):
        # `self._k, self._v = k, v` with dispatch-carrying locals binds
        # both attributes elementwise
        fs = run("""
            import jax

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f)

                def step(self, ids):
                    logits, k, v = self._jstep(ids)
                    self._k, self._v = k, v
                    return logits

                def swap_out(self):
                    return self._k.numpy(), self._v.numpy()
        """)
        assert rules_of(fs) == ["host-sync-in-traced"] * 2


# ---------------------------------------------------------------------------
# use-after-donate
# ---------------------------------------------------------------------------
class TestUseAfterDonate:
    def test_read_after_donation_flagged(self):
        fs = run("""
            import jax

            def go(f, x, y):
                step = jax.jit(f, donate_argnums=(0,))
                out = step(x, y)
                return x.sum()
        """)
        assert rules_of(fs) == ["use-after-donate"]
        assert "'x'" in fs[0].message

    def test_self_attr_binding_cross_method(self):
        fs = run("""
            import jax

            class Eng:
                def __init__(self, f, cache):
                    self._step = jax.jit(f, donate_argnums=(1,))
                    self._cache = cache

                def run(self, a):
                    out = self._step(a, self._cache)
                    return self._cache
        """)
        assert rules_of(fs) == ["use-after-donate"]
        assert "self._cache" in fs[0].message

    def test_conditional_donate_argnums_union(self):
        fs = run("""
            import jax

            def go(f, x, donate):
                step = jax.jit(f, donate_argnums=(0,) if donate else ())
                out = step(x)
                return x + 1
        """)
        assert rules_of(fs) == ["use-after-donate"]

    def test_near_miss_reassigned_before_reuse_clean(self):
        fs = run("""
            import jax

            def go(f, x):
                step = jax.jit(f, donate_argnums=(0,))
                x = step(x)
                return x + 1
        """)
        assert fs == []

    def test_near_miss_same_statement_rebind_clean(self):
        # the engine.py idiom: donated caches rebound by the same stmt
        fs = run("""
            import jax

            class Eng:
                def __init__(self, f):
                    self._jstep = jax.jit(f, donate_argnums=(0, 1))

                def step(self):
                    self._k, self._v = self._jstep(self._k, self._v)
                    return self._k
        """)
        assert fs == []

    def test_near_miss_else_branch_not_poisoned(self):
        # if/else are mutually exclusive: a donation in the `if` arm
        # must not kill the name for the `else` arm
        fs = run("""
            import jax

            def go(f, x, fast):
                step = jax.jit(f, donate_argnums=(0,))
                if fast:
                    y = step(x)
                else:
                    y = x + 1
                    z = x * 2
                return y
        """)
        assert fs == []

    def test_use_after_either_branch_donation_flagged(self):
        fs = run("""
            import jax

            def go(f, x, fast):
                step = jax.jit(f, donate_argnums=(0,))
                if fast:
                    y = step(x)
                else:
                    y = x + 1
                return x.sum()
        """)
        assert rules_of(fs) == ["use-after-donate"]

    def test_dead_name_passed_to_another_dispatch_flagged(self):
        # jax raises 'Array has been deleted' when a dead buffer feeds
        # ANY later dispatch, not just host code
        fs = run("""
            import jax

            def go(f, g, x):
                step = jax.jit(f, donate_argnums=(0,))
                other = jax.jit(g)
                y = step(x)
                return other(x)
        """)
        assert rules_of(fs) == ["use-after-donate"]

    def test_near_miss_undonated_jit_clean(self):
        fs = run("""
            import jax

            def go(f, x):
                step = jax.jit(f)
                out = step(x)
                return x + 1
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# trace-time-impurity
# ---------------------------------------------------------------------------
class TestTraceImpurity:
    def test_time_random_environ_in_traced(self):
        fs = run("""
            import jax
            import os
            import time
            import numpy as np

            @jax.jit
            def f(x):
                t = time.time()
                r = np.random.randn(3)
                e = os.environ["SEED"]
                g = os.environ.get("SEED2")
                return x * t
        """)
        assert rules_of(fs) == ["trace-time-impurity"] * 4

    def test_closure_mutation_in_traced(self):
        fs = run("""
            import jax

            losses = []
            cache = {}

            @jax.jit
            def f(x):
                losses.append(x)
                cache["last"] = x
                return x
        """)
        assert rules_of(fs) == ["trace-time-impurity"] * 2

    def test_scan_body_is_traced(self):
        fs = run("""
            import time

            import jax

            def body(carry, x):
                return carry + time.time(), None

            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """)
        assert rules_of(fs) == ["trace-time-impurity"]

    def test_near_miss_host_side_impurity_clean(self):
        fs = run("""
            import time
            import numpy as np

            def profile_step(fn):
                t0 = time.time()
                events = []
                events.append(fn())
                return time.time() - t0, np.random.rand()
        """)
        assert fs == []

    def test_pallas_ref_store_in_kernel_loop_body_is_clean(self):
        # a store into a kernel's Ref parameter is a traced store, also
        # from a loop body nested in the kernel; the list is still a
        # closure mutation
        fs = run("""
            import functools

            import jax
            from jax.experimental import pallas as pl

            seen = []

            def kernel(x_ref, o_ref, acc_ref, *, n):
                def body(i, carry):
                    acc_ref[i] = x_ref[i] + carry
                    seen.append(i)
                    return carry
                jax.lax.fori_loop(0, n, body, 0)
                o_ref[...] = acc_ref[...]

            def call(x):
                k = functools.partial(kernel, n=4)
                return pl.pallas_call(k, out_shape=x)(x)
        """)
        assert rules_of(fs) == ["trace-time-impurity"]
        assert "seen.append" in fs[0].snippet

    def test_nested_helper_local_does_not_mask_closure_mutation(self):
        # `hits` is bound only inside the nested helper: the OUTER
        # body's append is still a closure mutation
        fs = run("""
            import jax

            hits = []

            @jax.jit
            def step(x):
                def helper(y):
                    hits = [y]
                    return hits
                hits.append(x)
                return helper(x)
        """)
        assert rules_of(fs) == ["trace-time-impurity"]
        assert "hits.append" in fs[0].snippet

    def test_near_miss_local_list_in_traced_clean(self):
        fs = run("""
            import jax

            @jax.jit
            def f(xs):
                acc = []
                for x in xs:
                    acc.append(x * 2)  # local: trace-time unrolling, fine
                return acc
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# tensor-bool-branch
# ---------------------------------------------------------------------------
class TestTensorBool:
    def test_if_and_while_on_tensor(self):
        fs = run("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                y = jnp.sum(x)
                if y > 0:
                    y = y * 2
                while y < 10:
                    y = y + 1
                return y
        """)
        assert rules_of(fs) == ["tensor-bool-branch"] * 2

    def test_taint_through_arithmetic_and_methods(self):
        fs = run("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                nf = jnp.any(jnp.isnan(x))
                flag = nf | jnp.any(jnp.isinf(x))
                if flag:
                    return x * 0
                return x
        """)
        assert rules_of(fs) == ["tensor-bool-branch"]

    def test_near_miss_host_flag_clean(self):
        fs = run("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x, training):
                if training:          # host param: static under jit
                    x = x * 2
                y = jnp.sum(x)
                if y is None:         # identity test is host-safe
                    return x
                if x.ndim > 1:        # static attr, not a tracer
                    return y
                return y
        """)
        assert fs == []

    def test_for_loop_target_inherits_taint(self):
        fs = run("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(xs):
                grads = jnp.split(xs, 2)
                for g in grads:
                    if g.sum() > 0:
                        return g
                return xs
        """)
        assert rules_of(fs) == ["tensor-bool-branch"]

    def test_near_miss_untraced_function_clean(self):
        fs = run("""
            import jax.numpy as jnp

            def host_filter(x):
                y = jnp.sum(x)
                if y > 0:   # eager host code: legal (blocking) sync
                    return y
                return -y
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# counter-provider-leak
# ---------------------------------------------------------------------------
class TestCounterLeak:
    def test_register_without_unregister_flagged(self):
        fs = run("""
            from paddle_tpu import profiler

            class Metrics:
                def __init__(self):
                    profiler.register_counter_provider("m/x", lambda: 1)
        """)
        assert rules_of(fs) == ["counter-provider-leak"]

    def test_near_miss_weakref_finalize_clean(self):
        fs = run("""
            import weakref

            from paddle_tpu import profiler

            class Metrics:
                def __init__(self, owner):
                    profiler.register_counter_provider("m/x", lambda: 1)
                    weakref.finalize(
                        owner, profiler.unregister_counter_provider,
                        "m/x")
        """)
        assert fs == []

    def test_near_miss_direct_unregister_clean(self):
        fs = run("""
            from paddle_tpu.profiler import (
                register_counter_provider, unregister_counter_provider,
            )

            def attach(name):
                register_counter_provider(name, lambda: 0)

            def detach(name):
                unregister_counter_provider(name)
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# block-until-ready-in-loop
# ---------------------------------------------------------------------------
class TestBlockUntilReadyLoop:
    def test_function_spelling_in_for_loop(self):
        fs = run("""
            import jax

            def train(data, step, state):
                for batch in data:
                    state = step(state, batch)
                    jax.block_until_ready(state)
                return state
        """)
        assert rules_of(fs) == ["block-until-ready-in-loop"]
        assert "EVERY iteration" in fs[0].message

    def test_method_spelling_in_while_loop(self):
        fs = run("""
            def drain(q):
                while q:
                    out = q.pop()
                    out.block_until_ready()
        """)
        assert rules_of(fs) == ["block-until-ready-in-loop"]

    def test_comprehension_counts_as_loop(self):
        fs = run("""
            import jax

            def collect(outs):
                return [jax.block_until_ready(o) for o in outs]
        """)
        assert rules_of(fs) == ["block-until-ready-in-loop"]

    def test_near_miss_sync_after_loop_clean(self):
        # the fix pattern itself: one sync on the final value
        fs = run("""
            import jax

            def train(data, step, state):
                for batch in data:
                    state = step(state, batch)
                jax.block_until_ready(state)
                return state
        """)
        assert fs == []

    def test_near_miss_def_inside_loop_clean(self):
        # a function DEFINED under a loop is not executed per
        # iteration; flagging it would poison every closure factory
        fs = run("""
            import jax

            def make_waiters(arrays):
                waiters = []
                for a in arrays:
                    def wait(a=a):
                        jax.block_until_ready(a)
                    waiters.append(wait)
                return waiters
        """)
        assert fs == []

    def test_suppression_with_reason_honored(self):
        fs = run("""
            import jax

            def probe_loop(q):
                while True:
                    arrays = q.get()
                    jax.block_until_ready(arrays)  # tpulint: disable=block-until-ready-in-loop (prober parks on purpose)
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------
class TestSuppressions:
    def test_inline_with_reason_silences(self):
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x)  # tpulint: disable=host-sync-in-traced (fixture: testing the suppression path)
        """)
        assert fs == []

    def test_standalone_comment_covers_next_line(self):
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                # tpulint: disable=host-sync-in-traced (fixture reason)
                return np.asarray(x)
        """)
        assert fs == []

    def test_suppression_on_last_line_of_wrapped_statement(self):
        # auto-formatters wrap long lines: a trailing comment lands on
        # the statement's LAST physical line, which must still cover
        # the finding anchored at its first
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(out):
                host = np.asarray(
                    out)  # tpulint: disable=host-sync-in-traced (fixture: wrapped stmt)
                return host
        """)
        assert fs == []

    def test_missing_reason_is_bad_suppression(self):
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x)  # tpulint: disable=host-sync-in-traced
        """)
        assert rules_of(fs) == ["bad-suppression"]
        assert "reason" in fs[0].message

    def test_unknown_rule_is_bad_suppression(self):
        fs = run("""
            x = 1  # tpulint: disable=no-such-rule (whatever)
        """)
        assert rules_of(fs) == ["bad-suppression"]
        assert "no-such-rule" in fs[0].message

    def test_reason_may_contain_parentheses(self):
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x)  # tpulint: disable=host-sync-in-traced (see PR (2) notes)
        """)
        assert fs == []

    def test_docstring_mention_is_not_a_live_suppression(self):
        # documentation of the syntax inside a string literal must not
        # register (nor report bad-suppression for a reasonless example)
        fs = run('''
            def helper():
                """Docs: silence with  # tpulint: disable=host-sync-in-traced
                on the offending line."""
                return 1
        ''')
        assert fs == []

    def test_stacked_standalone_suppressions_all_apply(self):
        body = """
            import jax
            import numpy as np

            def go(f, x):
                step = jax.jit(f, donate_argnums=(0,))
                y = step(x)
                {s1}
                {s2}
                return np.asarray(y) + x.sum()
        """
        # unsuppressed: one finding per rule on the return line
        fs = run(body.format(s1="pass", s2="pass"))
        assert sorted(rules_of(fs)) == ["host-sync-in-traced",
                                        "use-after-donate"]
        # two stacked standalone disables both apply to the statement
        fs = run(body.format(
            s1="# tpulint: disable=use-after-donate (fixture: stack 1)",
            s2="# tpulint: disable=host-sync-in-traced (fixture: stack "
               "2)"))
        assert fs == []

    def test_wrong_rule_does_not_silence(self):
        fs = run("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x)  # tpulint: disable=use-after-donate (wrong rule on purpose)
        """)
        assert rules_of(fs) == ["host-sync-in-traced"]


# ---------------------------------------------------------------------------
# baseline + CLI
# ---------------------------------------------------------------------------
VIOLATING = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.asarray(x)
"""


class TestBaselineAndCli:
    def _write(self, tmp_path, name="bad.py", body=VIOLATING):
        p = tmp_path / name
        p.write_text(body)
        return str(p)

    def test_exit_codes_and_text_output(self, tmp_path, capsys):
        path = self._write(tmp_path)
        assert cli_main([path]) == 1
        out = capsys.readouterr().out
        assert "host-sync-in-traced" in out
        clean = self._write(tmp_path, "clean.py", "x = 1\n")
        assert cli_main([clean]) == 0
        assert cli_main([]) == 2
        assert cli_main([str(tmp_path / "missing.py")]) == 2
        assert cli_main([path, "--disable", "typo-rule"]) == 2

    def test_json_format(self, tmp_path, capsys):
        path = self._write(tmp_path)
        assert cli_main([path, "--format=json"]) == 1
        data = json.loads(capsys.readouterr().out)
        assert data["count"] == 1
        f = data["findings"][0]
        assert f["rule"] == "host-sync-in-traced"
        assert f["path"] == path
        assert f["line"] == 7

    def test_baseline_roundtrip(self, tmp_path, capsys):
        path = self._write(tmp_path)
        base = str(tmp_path / "baseline.json")
        assert cli_main([path, "--baseline", base,
                         "--write-baseline"]) == 0
        capsys.readouterr()
        # existing violation is baselined -> clean exit
        assert cli_main([path, "--baseline", base]) == 0
        out = capsys.readouterr().out
        assert "suppressed by baseline" in out
        # a NEW violation still fails even with the baseline
        with open(path, "a") as fh:
            fh.write("\n\n@jax.jit\ndef g(x):\n    return x.item()\n")
        assert cli_main([path, "--baseline", base]) == 1

    def test_baseline_survives_line_shifts(self, tmp_path):
        path = self._write(tmp_path)
        base = str(tmp_path / "baseline.json")
        findings = analyze_paths([path])
        write_baseline(base, findings)
        # prepend unrelated lines: fingerprints hash line TEXT, not
        # numbers
        body = open(path).read()
        with open(path, "w") as fh:
            fh.write("# a new header comment\nimport os  # noqa\n" + body)
        assert cli_main([path, "--baseline", base]) == 0
        assert len(load_baseline(base)) == 1

    def test_list_rules(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for name in get_rules():
            assert name in out

    def test_disable_rule(self, tmp_path):
        path = self._write(tmp_path)
        assert cli_main([path, "--disable",
                         "host-sync-in-traced"]) == 0

    def test_parse_error_reported_not_raised(self, tmp_path):
        path = self._write(tmp_path, "broken.py", "def f(:\n")
        fs = analyze_paths([path])
        assert rules_of(fs) == ["parse-error"]

    def test_non_utf8_file_reported_not_raised(self, tmp_path):
        bad = tmp_path / "latin1.py"
        bad.write_bytes("x = '\xe9'\n".encode("latin-1"))
        good = self._write(tmp_path, "ok.py", "x = 1\n")
        fs = analyze_paths([str(bad), good])
        assert rules_of(fs) == ["parse-error"]
        assert "cannot read" in fs[0].message
        assert cli_main([str(tmp_path)]) == 1  # reported, not crashed


# ---------------------------------------------------------------------------
# unlocked-shared-state (lockcheck)
# ---------------------------------------------------------------------------
class TestUnlockedSharedState:
    def test_thread_writes_main_reads_no_lock(self):
        fs = run("""
            import threading

            class Worker:
                def __init__(self):
                    self.count = 0
                    self._t = threading.Thread(target=self._loop,
                                               daemon=True)

                def start(self):
                    self._t.start()

                def _loop(self):
                    while True:
                        self.count += 1

                def snapshot(self):
                    return self.count
        """)
        assert rules_of(fs) == ["unlocked-shared-state"]
        assert "count" in fs[0].message
        assert "thread:_loop" in fs[0].message

    def test_near_miss_lock_on_both_sides_clean(self):
        fs = run("""
            import threading

            class Worker:
                def __init__(self):
                    self.count = 0
                    self._lock = threading.Lock()
                    self._t = threading.Thread(target=self._loop,
                                               daemon=True)

                def start(self):
                    self._t.start()

                def _loop(self):
                    while True:
                        with self._lock:
                            self.count += 1

                def snapshot(self):
                    with self._lock:
                        return self.count
        """)
        assert fs == []

    def test_near_miss_read_only_shared_attr_clean(self):
        # both roots only READ the attr: no write, no race
        fs = run("""
            import threading

            class Worker:
                def __init__(self, cfg):
                    self.cfg = cfg
                    self._t = threading.Thread(target=self._loop,
                                               daemon=True)

                def start(self):
                    self._t.start()

                def _loop(self):
                    print(self.cfg)

                def snapshot(self):
                    return self.cfg
        """)
        assert fs == []

    def test_near_miss_sync_object_attr_clean(self):
        # threading.Event is itself a synchronization primitive
        fs = run("""
            import threading

            class Worker:
                def __init__(self):
                    self._flag = threading.Event()
                    self._t = threading.Thread(target=self._loop,
                                               daemon=True)

                def start(self):
                    self._t.start()

                def _loop(self):
                    self._flag.set()

                def done(self):
                    return self._flag.is_set()
        """)
        assert fs == []

    def test_timer_and_finalizer_count_as_roots(self):
        fs = run("""
            import threading
            import weakref

            class Cache:
                def __init__(self, obj):
                    self.hits = 0
                    weakref.finalize(obj, self._evict)
                    self._timer = threading.Timer(5.0, self._tick)

                def _evict(self):
                    self.hits = 0

                def _tick(self):
                    self.hits += 1

                def lookup(self):
                    self.hits += 1
        """)
        assert rules_of(fs) == ["unlocked-shared-state"]

    def test_peer_listener_unlocked_inbox_flagged(self):
        # the peer-listener concurrency root pattern (ISSUE 15): an
        # accept-loop thread staging frames into an inbox dict the
        # service loop pops from — unlocked, that's a real race
        fs = run("""
            import threading

            class Listener:
                def __init__(self):
                    self._inbox = {}
                    self._t = threading.Thread(target=self._serve,
                                               daemon=True)
                    self._t.start()

                def _serve(self):
                    while True:
                        self._inbox = dict(self._inbox, t1=b"frame")

                def take(self, ticket_id):
                    return self._inbox.pop(ticket_id, None)
        """)
        assert rules_of(fs) == ["unlocked-shared-state"]
        assert "_inbox" in fs[0].message

    def test_peer_listener_locked_inbox_clean(self):
        # near miss: the shipped PeerListener discipline — every inbox
        # touch under one lock, socket IO outside it — is clean
        fs = run("""
            import threading

            class Listener:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._inbox = {}
                    self._t = threading.Thread(target=self._serve,
                                               daemon=True)
                    self._t.start()

                def _serve(self):
                    while True:
                        with self._lock:
                            self._inbox = dict(self._inbox, t1=b"f")

                def take(self, ticket_id):
                    with self._lock:
                        return self._inbox.pop(ticket_id, None)
        """)
        assert fs == []

    def test_suppression_with_reason_honored(self):
        fs = run("""
            import threading

            class Worker:
                def __init__(self):
                    self.count = 0
                    self._t = threading.Thread(target=self._loop,
                                               daemon=True)

                def start(self):
                    self._t.start()

                def _loop(self):
                    self.count += 1  # tpulint: disable=unlocked-shared-state (joined before any read)

                def snapshot(self):
                    return self.count
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# lock-order-cycle
# ---------------------------------------------------------------------------
class TestLockOrderCycle:
    def test_inverted_pair_flagged(self):
        fs = run("""
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
        """)
        assert rules_of(fs) == ["lock-order-cycle"]
        assert "->" in fs[0].message

    def test_near_miss_consistent_order_clean(self):
        fs = run("""
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ab2(self):
                    with self._a:
                        with self._b:
                            pass
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------
class TestBlockingUnderLock:
    def test_sleep_inside_with_lock(self):
        fs = run("""
            import threading
            import time

            class Poller:
                def __init__(self):
                    self._lock = threading.Lock()

                def poll(self):
                    with self._lock:
                        time.sleep(1.0)
        """)
        assert rules_of(fs) == ["blocking-under-lock"]
        assert "_lock" in fs[0].message

    def test_store_rpc_inside_registry_lock(self):
        fs = run("""
            import threading

            class Registry:
                def __init__(self, store):
                    self._lock = threading.Lock()
                    self._store = store

                def publish(self, k, v):
                    with self._lock:
                        self._store.set(k, v)
        """)
        assert rules_of(fs) == ["blocking-under-lock"]

    def test_near_miss_sleep_after_release_clean(self):
        fs = run("""
            import threading
            import time

            class Poller:
                def __init__(self):
                    self._lock = threading.Lock()

                def poll(self):
                    with self._lock:
                        x = 1
                    time.sleep(1.0)
        """)
        assert fs == []

    def test_lease_renew_store_write_under_lock(self):
        # the trap the replicated control plane's LeaseStore avoids by
        # being lock-free: a store write (an RPC on FileStore/TCPStore
        # backends) inside the lease mutex would serialize every
        # renew-before-emit on the slowest store round-trip
        fs = run("""
            import threading

            class LockedLeaseStore:
                def __init__(self, store):
                    self._lock = threading.Lock()
                    self._store = store
                    self._seq = {}

                def renew(self, rid, rec):
                    with self._lock:
                        self._seq[rid] = self._seq.get(rid, 0) + 1
                        rec["seq"] = self._seq[rid]
                        self._store.set(rid, rec)
        """)
        assert rules_of(fs) == ["blocking-under-lock"]
        assert "self._store.set()" in fs[0].message
        assert "LockedLeaseStore._lock" in fs[0].message

    def test_near_miss_lease_seq_under_lock_write_after_clean(self):
        # the correct shape: bump the sequence under the lock, release,
        # THEN do the store round-trip with the captured value
        fs = run("""
            import threading

            class LeaseStore:
                def __init__(self, store):
                    self._lock = threading.Lock()
                    self._store = store
                    self._seq = {}

                def renew(self, rid, rec):
                    with self._lock:
                        self._seq[rid] = self._seq.get(rid, 0) + 1
                        seq = self._seq[rid]
                    rec["seq"] = seq
                    self._store.set(rid, rec)
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# signal-handler-unsafe
# ---------------------------------------------------------------------------
class TestSignalHandlerUnsafe:
    def test_store_rpc_in_handler(self):
        fs = run("""
            import signal

            class Mon:
                def __init__(self, store):
                    self._store = store

                def install(self):
                    signal.signal(signal.SIGTERM, self._on_term)

                def _on_term(self, signum, frame):
                    self._store.set("preempt", "1")
        """)
        assert rules_of(fs) == ["signal-handler-unsafe"]
        assert "_on_term" in fs[0].message

    def test_lock_acquire_in_handler_callee(self):
        # reached transitively: handler -> self._record() -> with lock
        fs = run("""
            import signal
            import threading

            class Mon:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def install(self):
                    signal.signal(signal.SIGTERM, self._on_term)

                def _on_term(self, signum, frame):
                    self._record()

                def _record(self):
                    with self._lock:
                        self.n += 1
        """)
        assert "signal-handler-unsafe" in rules_of(fs)

    def test_near_miss_flag_only_handler_clean(self):
        fs = run("""
            import signal
            import threading

            class Mon:
                def __init__(self):
                    self._flag = threading.Event()

                def install(self):
                    signal.signal(signal.SIGTERM, self._on_term)

                def _on_term(self, signum, frame):
                    self._flag.set()
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# collective-divergence
# ---------------------------------------------------------------------------
class TestCollectiveDivergence:
    def test_psum_under_rank_branch_in_shard_map(self):
        fs = run("""
            import jax
            from jax import lax
            from jax.experimental.shard_map import shard_map

            def body(x):
                if lax.axis_index("dp") == 0:
                    x = lax.psum(x, "dp")
                return x

            f = shard_map(body, mesh=None, in_specs=None,
                          out_specs=None)
        """)
        assert "collective-divergence" in rules_of(fs)
        f = [x for x in fs if x.rule == "collective-divergence"][0]
        assert "psum" in f.message and "deadlock" in f.message

    def test_collective_inside_cond_branch(self):
        fs = run("""
            import jax
            from jax import lax

            @jax.jit
            def step(x, p):
                def tru(x):
                    return lax.psum(x, "dp")
                def fls(x):
                    return x
                return lax.cond(p, tru, fls, x)
        """)
        assert rules_of(fs) == ["collective-divergence"]
        assert "lax.cond" in fs[0].message

    def test_near_miss_hoisted_collective_clean(self):
        # the fix pattern: every rank issues the collective
        fs = run("""
            import jax
            from jax import lax
            from jax.experimental.shard_map import shard_map

            def body(x):
                s = lax.psum(x, "dp")
                return s

            f = shard_map(body, mesh=None, in_specs=None,
                          out_specs=None)
        """)
        assert fs == []

    def test_near_miss_host_static_branch_clean(self):
        # `if causal:` is a Python bool closed over at trace time —
        # every rank traces the same arm
        fs = run("""
            import jax
            from jax import lax
            from jax.experimental.shard_map import shard_map

            def make(causal):
                def body(x):
                    if causal:
                        x = lax.psum(x, "dp")
                    return x
                return shard_map(body, mesh=None, in_specs=None,
                                 out_specs=None)
        """)
        assert fs == []

    def test_near_miss_host_code_clean(self):
        # no traced scope at all: a collective name in host code is
        # someone else's problem (it would fail loudly anyway)
        fs = run("""
            from jax import lax

            def host(x, rank):
                if rank == 0:
                    return lax.psum(x, "dp")
                return x
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# finish-reason-literal
# ---------------------------------------------------------------------------
class TestFinishReasonLiteral:
    def test_unknown_literal_in_abort_call(self):
        fs = run("""
            from paddle_tpu.serving.request import Request

            def kill(eng, rid):
                eng.abort(rid, "expire")
        """)
        assert rules_of(fs) == ["finish-reason-literal"]
        assert "'expire'" in fs[0].message

    def test_unknown_literal_in_assignment_and_kwarg(self):
        fs = run("""
            from paddle_tpu.serving.request import Request

            def finish(req, eng, rid):
                req.finish_reason = "aborted:oom"
                eng._finalize(rid, finish_reason="done")
        """)
        assert rules_of(fs) == ["finish-reason-literal"] * 2

    def test_near_miss_vocabulary_literal_clean(self):
        fs = run("""
            from paddle_tpu.serving.request import Request

            def kill(eng, rid):
                eng.abort(rid, "aborted:user")
        """)
        assert fs == []

    def test_near_miss_module_without_serving_import_clean(self):
        # the vocabulary only applies where serving.request is in play
        fs = run("""
            def kill(eng, rid):
                eng.abort(rid, "expire")
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# lockcheck rules: baseline + CLI integration
# ---------------------------------------------------------------------------
RACY = """import threading


class Worker:
    def __init__(self):
        self.count = 0
        self._t = threading.Thread(target=self._loop, daemon=True)

    def start(self):
        self._t.start()

    def _loop(self):
        self.count += 1

    def snapshot(self):
        return self.count
"""


class TestLockcheckBaselineAndCli:
    def test_new_rule_findings_baseline_roundtrip(self, tmp_path, capsys):
        path = tmp_path / "racy.py"
        path.write_text(RACY)
        base = str(tmp_path / "baseline.json")
        assert cli_main([str(path)]) == 1
        capsys.readouterr()
        assert cli_main([str(path), "--baseline", base,
                         "--write-baseline"]) == 0
        capsys.readouterr()
        assert cli_main([str(path), "--baseline", base]) == 0

    def test_only_flag_restricts_rule_set(self, tmp_path, capsys):
        path = tmp_path / "racy.py"
        path.write_text(RACY)
        assert cli_main([str(path), "--only",
                         "unlocked-shared-state"]) == 1
        out = capsys.readouterr().out
        assert "unlocked-shared-state" in out
        assert cli_main([str(path), "--only", "lock-order-cycle"]) == 0
        assert cli_main([str(path), "--only", "typo-rule"]) == 2

    def test_only_does_not_hide_bad_suppressions(self, tmp_path):
        # meta rules stay active under --only: a reasonless suppression
        # must not sneak in through a narrowed lint run
        path = tmp_path / "sup.py"
        path.write_text(
            "import os\n"
            "x = os.getpid()  # tpulint: disable=host-sync-in-traced\n")
        assert cli_main([str(path), "--only", "lock-order-cycle"]) == 1

    def test_write_baseline_order_independent(self, tmp_path):
        """Identical trees must produce byte-identical baselines no
        matter how the caller ordered the findings (occurrence
        numbering is order-sensitive without the internal sort)."""
        path = tmp_path / "racy.py"
        # two identical racy lines -> identical snippets -> occurrence
        # disambiguation kicks in
        path.write_text(RACY.replace(
            "        self.count += 1\n",
            "        self.count += 1\n        self.count += 1\n"))
        findings = analyze_paths([str(path)])
        assert len(findings) >= 1
        b1, b2 = str(tmp_path / "b1.json"), str(tmp_path / "b2.json")
        write_baseline(b1, findings)
        write_baseline(b2, list(reversed(findings)))
        assert open(b1).read() == open(b2).read()


# ---------------------------------------------------------------------------
# leaked-resource-on-raise (flowcheck)
# ---------------------------------------------------------------------------
class TestLeakedResource:
    def test_pr14_import_kv_scatter_leak_flagged(self):
        """Re-introducing the PR 14 bug — blocks landed, scatter faults,
        no rollback — must be caught at commit time, not by chaos."""
        fs = run("""
            class Engine:
                def import_kv(self, request_id, blocks, frames):
                    self.block_manager.import_blocks(request_id, blocks)
                    self._scatter(frames)
                    self.sessions[request_id] = blocks
        """)
        assert rules_of(fs) == ["leaked-resource-on-raise"]
        assert "import_blocks" in fs[0].message

    def test_rollback_in_except_then_reraise_clean(self):
        """The PR 14 FIX shape: release in the handler, re-raise."""
        fs = run("""
            class Engine:
                def import_kv(self, request_id, blocks, frames):
                    self.block_manager.import_blocks(request_id, blocks)
                    try:
                        self._scatter(frames)
                    except Exception:
                        self.block_manager.free(request_id)
                        raise
                    self.sessions[request_id] = blocks
        """)
        assert rules_of(fs) == []

    def test_release_in_finally_clean(self):
        fs = run("""
            class Probe:
                def measure(self, request_id):
                    self.block_manager.allocate(request_id, 4)
                    try:
                        self._touch(request_id)
                    finally:
                        self.block_manager.free(request_id)
        """)
        assert rules_of(fs) == []

    def test_swallowing_handler_releases_clean(self):
        fs = run("""
            class Sched:
                def admit(self, req):
                    self.block_manager.allocate(req.request_id, 4)
                    try:
                        self._kick()
                    except Exception:
                        self.block_manager.free(req.request_id)
                        return
                    self.running.append(req)
        """)
        assert rules_of(fs) == []

    def test_conditional_release_still_flagged(self):
        """A release under only one branch does not cover the raise
        edge — held-on-any-path merging."""
        fs = run("""
            class Sched:
                def admit(self, req, ok):
                    self.block_manager.allocate(req.request_id, 4)
                    if ok:
                        self.block_manager.free(req.request_id)
                    self._kick()
        """)
        assert rules_of(fs) == ["leaked-resource-on-raise"]

    def test_transfer_before_fallible_call_clean(self):
        fs = run("""
            class Sched:
                def admit(self, req):
                    self.block_manager.allocate(req.request_id, 4)
                    self.running.append(req)
                    self._kick()
        """)
        assert rules_of(fs) == []

    def test_swap_out_host_slots_pairing(self):
        fs = run("""
            class Sched:
                def evict(self, victim):
                    self.block_manager.swap_out(victim.request_id, 2)
                    self._copy(victim)
                    self.swapped.append(victim)
        """)
        assert rules_of(fs) == ["leaked-resource-on-raise"]
        assert "swap_out" in fs[0].message


# ---------------------------------------------------------------------------
# counter-snapshot-drift (flowcheck)
# ---------------------------------------------------------------------------
class TestCounterDrift:
    def test_bumped_but_never_read_flagged(self):
        fs = run_at("""
            class Sched:
                def step(self):
                    self.num_zz_invisible_counter += 1
        """, "paddle_tpu/serving/fixture.py")
        assert rules_of(fs) == ["counter-snapshot-drift"]
        assert "num_zz_invisible_counter" in fs[0].message

    def test_counter_with_real_reader_clean(self):
        # num_swap_outs is surfaced by the serving metrics layer
        fs = run_at("""
            class Sched:
                def step(self):
                    self.num_swap_outs += 1
        """, "paddle_tpu/serving/fixture.py")
        assert rules_of(fs) == []

    def test_out_of_scope_module_ignored(self):
        fs = run_at("""
            class Opt:
                def step(self):
                    self.num_zz_invisible_counter += 1
        """, "paddle_tpu/optimizer/fixture.py")
        assert rules_of(fs) == []

    def test_gauge_without_getter_flagged(self):
        fs = run_at("""
            class M:
                GAUGES = ("good", "orphan")
                _E_GAUGES = {"good": lambda e: e.num_swap_outs}
        """, "paddle_tpu/serving/fixture_metrics.py")
        assert rules_of(fs) == ["counter-snapshot-drift"]
        assert "orphan" in fs[0].message

    def test_getter_key_missing_from_gauges_flagged(self):
        fs = run_at("""
            class M:
                GAUGES = ("good",)
                _E_GAUGES = {"good": lambda e: e.num_swap_outs,
                             "stray": lambda e: e.num_swap_outs}
        """, "paddle_tpu/serving/fixture_metrics.py")
        assert rules_of(fs) == ["counter-snapshot-drift"]
        assert "stray" in fs[0].message

    def test_ghost_gauge_flagged(self):
        fs = run_at("""
            class M:
                GAUGES = ("g",)
                _E_GAUGES = {"g": lambda e: e.num_zz_ghost_counter}
        """, "paddle_tpu/serving/fixture_metrics.py")
        assert rules_of(fs) == ["counter-snapshot-drift"]
        assert "never assigned" in fs[0].message

    def test_coherent_metrics_class_clean(self):
        fs = run_at("""
            class M:
                GAUGES = ("g", "chain")
                _E_GAUGES = {"g": lambda e: e.num_swap_outs}

                def provider(self, name):
                    if name == "chain":
                        return 0
        """, "paddle_tpu/serving/fixture_metrics.py")
        assert rules_of(fs) == []


# ---------------------------------------------------------------------------
# fault-point-literal (flowcheck)
# ---------------------------------------------------------------------------
class TestFaultPointLiteral:
    def test_raw_literal_call_site_flagged(self):
        fs = run("""
            from paddle_tpu.testing import faults

            class Engine:
                def step(self):
                    faults.fire("serving.step")
        """)
        assert rules_of(fs) == ["fault-point-literal"]
        assert "serving.step" in fs[0].message

    def test_literal_led_fstring_flagged(self):
        fs = run("""
            from paddle_tpu.testing import faults

            class BM:
                def allocate(self, request_id):
                    faults.check(f"serving.force_oom.{request_id}")
        """)
        assert rules_of(fs) == ["fault-point-literal"]

    def test_registry_constant_forms_clean(self):
        fs = run("""
            from paddle_tpu.testing import faults

            class Engine:
                def step(self, request_id):
                    faults.fire(faults.SERVING_STEP)
                    faults.check(
                        f"{faults.SERVING_FORCE_OOM}.{request_id}")
        """)
        assert rules_of(fs) == []

    def test_unrelated_fire_method_clean(self):
        fs = run("""
            class Trigger:
                def pull(self):
                    self.gun.fire("bang")
        """)
        assert rules_of(fs) == []

    def test_unreferenced_registry_point_flagged(self):
        """Direction 2: a FAULT_POINTS member no test or script ever
        mentions is dead chaos surface."""
        # the coverage corpus includes THIS file, so the dead point's
        # name is assembled at runtime to keep it out of the corpus
        dead = "zz.nobody_" + "ever_installs"
        fs = run(f"""
            ZZ = "{dead}"
            OK = "fleet.slow_replica"
            FAULT_POINTS = frozenset({{ZZ, OK}})
        """)
        assert rules_of(fs) == ["fault-point-literal"]
        assert dead in fs[0].message

    def test_covered_registry_clean(self):
        fs = run("""
            A = "fleet.slow_replica"
            B = "ckpt.committed"
            FAULT_POINTS = frozenset({A, B})
        """)
        assert rules_of(fs) == []


# ---------------------------------------------------------------------------
# rpc-verb-unclassified (flowcheck)
# ---------------------------------------------------------------------------
SERVICER_HEAD = """
    IDEMPOTENT_METHODS = frozenset({"ping"})
    MUTATION_METHODS = frozenset({"step"})

    class WorkerServicer:
        def _dispatch(self, method, args):
            if method == "ping":
                return "pong"
            if method == "step":
                return self.eng.step()
"""


class TestRpcVerbUnclassified:
    def test_unclassified_dispatch_arm_flagged(self):
        # the PR 19 tier_stats shape: dispatched, classified nowhere
        fs = run(SERVICER_HEAD + """\
                if method == "tier_stats":
                    return self.eng.stats()
        """)
        assert rules_of(fs) == ["rpc-verb-unclassified"]
        assert "tier_stats" in fs[0].message

    def test_total_partition_clean(self):
        fs = run(SERVICER_HEAD)
        assert rules_of(fs) == []

    def test_verb_in_both_sets_flagged(self):
        fs = run("""
            IDEMPOTENT_METHODS = frozenset({"ping", "step"})
            MUTATION_METHODS = frozenset({"step"})

            class WorkerServicer:
                def _dispatch(self, method, args):
                    if method == "ping":
                        return "pong"
                    if method == "step":
                        return self.eng.step()
        """)
        assert rules_of(fs) == ["rpc-verb-unclassified"]
        assert "BOTH" in fs[0].message

    def test_stale_set_entry_flagged(self):
        fs = run("""
            IDEMPOTENT_METHODS = frozenset({"ping", "vanished"})
            MUTATION_METHODS = frozenset()

            class WorkerServicer:
                def _dispatch(self, method, args):
                    if method == "ping":
                        return "pong"
        """)
        assert rules_of(fs) == ["rpc-verb-unclassified"]
        assert "vanished" in fs[0].message

    def test_one_sided_partition_flagged(self):
        fs = run("""
            IDEMPOTENT_METHODS = frozenset({"ping"})

            class WorkerServicer:
                def _dispatch(self, method, args):
                    if method == "ping":
                        return "pong"
        """)
        assert rules_of(fs) == ["rpc-verb-unclassified"]
        assert "one-sided" in fs[0].message

    def test_module_without_servicer_clean(self):
        fs = run("""
            IDEMPOTENT_METHODS = frozenset({"stale_but_unchecked"})

            class Plain:
                def _dispatch(self, method, args):
                    return None
        """)
        # Plain is not a *Servicer: the rule stays out of non-RPC code
        assert rules_of(fs) == []


# ---------------------------------------------------------------------------
# unbounded-rpc-deadline (flowcheck)
# ---------------------------------------------------------------------------
class TestRpcDeadline:
    def test_call_without_deadline_flagged(self):
        fs = run("""
            class Handle:
                def ping(self):
                    return self.client.call("ping", {})
        """)
        assert rules_of(fs) == ["unbounded-rpc-deadline"]
        assert "deadline_s" in fs[0].message

    def test_call_with_deadline_clean(self):
        fs = run("""
            class Handle:
                def ping(self):
                    return self.client.call("ping", {}, deadline_s=5.0)
        """)
        assert rules_of(fs) == []

    def test_splat_kwargs_clean(self):
        fs = run("""
            class Handle:
                def ping(self, **kw):
                    return self.rpc_client.call("ping", {}, **kw)
        """)
        assert rules_of(fs) == []

    def test_non_client_receiver_clean(self):
        fs = run("""
            class Handle:
                def ping(self):
                    return self.conn.call("ping", {})
        """)
        assert rules_of(fs) == []

    def test_ticket_without_deadline_ms_flagged(self):
        fs = run("""
            class Router:
                def ship(self, src, dst, rid):
                    return self._issue_ticket(src, dst, rid)
        """)
        assert rules_of(fs) == ["unbounded-rpc-deadline"]
        assert "deadline_ms" in fs[0].message

    def test_ticket_with_deadline_ms_clean(self):
        fs = run("""
            class Router:
                def ship(self, src, dst, rid):
                    return self._issue_ticket(
                        src, dst, rid,
                        deadline_ms=self._rung_deadline_ms(1))
        """)
        assert rules_of(fs) == []


# ---------------------------------------------------------------------------
# flowcheck rules through the CLI: --only, baseline, github, --stats
# ---------------------------------------------------------------------------
LEAKY = textwrap.dedent("""\
    class Engine:
        def import_kv(self, request_id, blocks, frames):
            self.block_manager.import_blocks(request_id, blocks)
            self._scatter(frames)
            self.sessions[request_id] = blocks
""")


class TestFlowcheckCli:
    def test_only_selects_flowcheck_rule(self, tmp_path, capsys):
        p = tmp_path / "leaky.py"
        p.write_text(LEAKY + "\nimport jax\n\n@jax.jit\ndef f(x):\n"
                     "    return x.item()\n")
        assert cli_main([str(p), "--only",
                         "leaked-resource-on-raise"]) == 1
        out = capsys.readouterr().out
        assert "leaked-resource-on-raise" in out
        assert "host-sync-in-traced" not in out

    def test_baseline_roundtrip_flowcheck(self, tmp_path, capsys):
        p = tmp_path / "leaky.py"
        p.write_text(LEAKY)
        base = str(tmp_path / "b.json")
        assert cli_main([str(p), "--baseline", base,
                         "--write-baseline"]) == 0
        capsys.readouterr()
        assert cli_main([str(p), "--baseline", base]) == 0
        # a new leak is NOT absorbed by the old baseline
        p.write_text(LEAKY + textwrap.dedent("""\

            class Probe:
                def grab(self, request_id):
                    self.block_manager.allocate(request_id, 4)
                    self._touch(request_id)
        """))
        assert cli_main([str(p), "--baseline", base]) == 1

    def test_github_format_annotations(self, tmp_path, capsys):
        p = tmp_path / "leaky.py"
        p.write_text(LEAKY)
        assert cli_main([str(p), "--format=github"]) == 1
        out = capsys.readouterr().out
        assert f"::error file={p}," in out
        assert "::leaked-resource-on-raise:" in out
        assert "line=3," in out

    def test_stats_counts_suppressions(self, tmp_path, capsys):
        p = tmp_path / "leaky.py"
        p.write_text(LEAKY.replace(
            "self.block_manager.import_blocks(request_id, blocks)",
            "self.block_manager.import_blocks(request_id, blocks)"
            "  # tpulint: disable=leaked-resource-on-raise (fixture)"))
        assert cli_main([str(p), "--stats"]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out
        assert "leaked-resource-on-raise" in out
        # the table row shows 0 findings / 1 suppression
        row = [ln for ln in out.splitlines()
               if ln.startswith("leaked-resource-on-raise")][0]
        assert row.split()[-2:] == ["0", "1"]
