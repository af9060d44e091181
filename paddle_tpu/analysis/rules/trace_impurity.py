"""trace-time-impurity: host state read/written during trace.

A traced function's Python body runs ONCE, at trace time. Anything it
reads from ambient host state is frozen into the executable forever:
``time.time()`` becomes a constant timestamp, ``np.random.*`` a
constant "random" draw (every compiled step reuses it — the classic
silently-wrong dropout), ``os.environ`` a config value that ignores
later changes. Mutating a closed-over list/dict is the dual failure:
the append runs once per TRACE, not once per step, so counters and
caches go quietly wrong the moment XLA stops retracing.

A Pallas kernel's ``Ref`` parameters are the exception: ``ref[i] = v``
is a traced store, also from a loop body nested in the kernel, so
stores into the parameters of a function handed to ``pallas_call``
(directly, through ``functools.partial``, or through a name bound to
one) are not findings.

In-graph alternatives: thread RNG keys (``jax.random.split``), pass
timestamps/config in as arguments, return accumulated values instead of
appending to closures.
"""
from __future__ import annotations

import ast
from typing import List, Set

from paddle_tpu.analysis.context import walk_own
from paddle_tpu.analysis.registry import Finding, register

_IMPURE_CALLS = {
    "time.time": "wall-clock read",
    "time.time_ns": "wall-clock read",
    "time.monotonic": "wall-clock read",
    "time.monotonic_ns": "wall-clock read",
    "time.perf_counter": "wall-clock read",
    "time.perf_counter_ns": "wall-clock read",
    "datetime.datetime.now": "wall-clock read",
    "datetime.datetime.utcnow": "wall-clock read",
    "os.getenv": "environment read",
    "os.environ.get": "environment read",
    "uuid.uuid4": "host RNG draw",
}
_IMPURE_PREFIXES = {
    "numpy.random.": "host RNG draw",
    "random.": "host RNG draw",
}
_MUTATORS = ("append", "extend", "insert", "add", "update", "setdefault",
             "pop", "popitem", "remove", "discard", "clear")

_DOC = __doc__


def _local_bindings(fdef: ast.AST) -> Set[str]:
    """Names bound in ``fdef``'s OWN scope (params + assignments +
    loop/with targets + nested def names) — everything NOT closed
    over. Nested functions' internals are excluded: a name bound only
    inside a helper must not mask the outer body's closure mutation."""
    out: Set[str] = set()
    a = fdef.args
    for arg in (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)):
        out.add(arg.arg)
    if a.vararg:
        out.add(a.vararg.arg)
    if a.kwarg:
        out.add(a.kwarg.arg)

    def collect_target(t):
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect_target(e)
        elif isinstance(t, ast.Starred):
            collect_target(t.value)

    for node in walk_own(fdef):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                collect_target(t)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            collect_target(node.target)
        elif isinstance(node, ast.For):
            collect_target(node.target)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            collect_target(node.optional_vars)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for al in node.names:
                out.add((al.asname or al.name).split(".")[0])
    # nested def NAMES are bindings in this scope (their bodies aren't)
    for node in ast.walk(fdef):
        if node is not fdef and \
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(node.name)
    return out


def _pallas_kernel_refs(tree: ast.AST) -> dict:
    """id(nested function def) -> the Ref parameter names of the Pallas
    kernel it is nested in. A kernel is a function of this module whose
    name is the first argument of a ``pallas_call(...)``, bare, wrapped
    in ``partial(...)``, or bound to such a ``partial`` by assignment."""
    def target(node):
        if isinstance(node, ast.Call) and node.args and getattr(
                node.func, "attr", getattr(node.func, "id", "")) == "partial":
            node = node.args[0]
        return node.id if isinstance(node, ast.Name) else None

    bound = {t.id: target(node.value) for node in ast.walk(tree)
             if isinstance(node, ast.Assign) for t in node.targets
             if isinstance(t, ast.Name) and isinstance(node.value, ast.Call)}
    kernels = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args and getattr(
                node.func, "attr", getattr(node.func, "id", "")) \
                == "pallas_call":
            name = target(node.args[0])
            kernels.add(bound.get(name) or name)
    out = {}
    for fdef in ast.walk(tree):
        if isinstance(fdef, ast.FunctionDef) and fdef.name in kernels:
            a = fdef.args
            refs = {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
            for inner in ast.walk(fdef):
                if inner is not fdef and isinstance(inner, ast.FunctionDef):
                    out[id(inner)] = refs
    return out


def _impure_call(module, call: ast.Call):
    canon = module.canonical(call.func)
    if canon is None:
        return None
    if canon in _IMPURE_CALLS:
        return canon, _IMPURE_CALLS[canon]
    for prefix, what in _IMPURE_PREFIXES.items():
        if canon.startswith(prefix):
            return canon, what
    return None


@register(
    "trace-time-impurity",
    "time/np.random/os.environ reads or closure mutation under trace",
    _DOC)
def check(module) -> List[Finding]:
    out: List[Finding] = []
    seen = set()
    imported = set(module.imports.aliases)

    for node in ast.walk(module.tree):
        reason = None
        # impure host reads anywhere in a traced region
        if isinstance(node, ast.Call):
            hit = _impure_call(module, node)
            if hit is not None:
                reason = module.trace_reason(node)
                if reason is not None:
                    canon, what = hit
                    out.append(module.finding(
                        "trace-time-impurity", node,
                        f"{canon}() is a {what} — it runs ONCE at trace "
                        f"time and its value is baked into the compiled "
                        f"graph ({reason}); pass it in as an argument "
                        f"or use a traced jax.random key"))
                    seen.add(id(node))
                    continue
        # os.environ[...] subscript reads
        if isinstance(node, ast.Subscript) and \
                module.canonical(node.value) == "os.environ" and \
                isinstance(getattr(node, "ctx", None), ast.Load):
            reason = module.trace_reason(node)
            if reason is not None:
                out.append(module.finding(
                    "trace-time-impurity", node,
                    f"os.environ read is frozen at trace time "
                    f"({reason}); resolve config before tracing and "
                    f"pass it in"))

    # closure mutation: per traced function, mutating method calls /
    # subscript stores on names NOT bound in the function's own scope
    kernel_refs = _pallas_kernel_refs(module.tree)
    for fdef in module.traces.traced_functions():
        if isinstance(fdef, ast.Lambda):
            continue
        local = _local_bindings(fdef)
        refs = kernel_refs.get(id(fdef), ())
        # shallow walk: a nested helper's statements are judged against
        # ITS locals by its own pass, not against this scope's
        for node in walk_own(fdef):
            if id(node) in seen:
                continue
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS and \
                    isinstance(node.func.value, ast.Name):
                name = node.func.value.id
                if name not in local and name not in imported and \
                        name != "self":
                    seen.add(id(node))
                    out.append(module.finding(
                        "trace-time-impurity", node,
                        f"'{name}.{node.func.attr}(...)' mutates a "
                        f"closed-over container inside a traced body — "
                        f"it runs once per TRACE, not once per step; "
                        f"return the value instead"))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                tgts = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in tgts:
                    if isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id not in local and \
                            t.value.id not in imported and \
                            t.value.id not in refs and \
                            id(t) not in seen:
                        seen.add(id(t))
                        out.append(module.finding(
                            "trace-time-impurity", t,
                            f"subscript store into closed-over "
                            f"'{t.value.id}' inside a traced body — a "
                            f"trace-time side effect that will not "
                            f"re-run per step; return the value "
                            f"instead"))
    # dedupe across parent/nested traced function double-visits
    uniq, keys = [], set()
    for f in out:
        k = (f.line, f.col, f.message)
        if k not in keys:
            keys.add(k)
            uniq.append(f)
    return uniq
