"""host-sync-in-traced: device->host copies on the hot path.

The PR-2 copy_frac hunt found 55% of device time going to transfers —
every one ultimately a Python-level ``.numpy()`` / ``.item()`` /
``float(t)`` / ``np.asarray(t)`` that forces the device queue to drain
and ships a buffer to host. Two placements are flagged:

* inside a TRACED function (``@jax.jit``, ``functionalize``,
  ``to_static``, and anything the trace index reaches): a host
  conversion of a tracer either crashes at trace time
  (ConcretizationTypeError) or — worse — silently bakes a constant into
  the compiled graph;
* on the DIRECT RESULT of a compiled dispatch (a name assigned from a
  call to a ``jax.jit(...)`` binding, including ``self._step``-style
  attributes bound elsewhere in the class): a per-step fetch in host
  driver code, the exact shape of the serving engine's per-step
  B×vocab logits pull. These are sometimes legitimate (a scalar loss, a
  B-sized token vector) — suppress with a reason when they are.

The dispatch-result placement tracks results ACROSS methods of a class:
``self._last = self._step_fn(...)`` (directly, or via a local name still
carrying the dispatch result) marks ``self._last`` dispatch-carrying
class-wide, so ``np.asarray(self._last)`` in a different method is
flagged too. An attribute REASSIGNED from anything non-dispatch
anywhere in the class is conservatively cleared (method execution order
is unknowable statically), and plain ``self._last = None``
initializers don't clear — they are the standard ``__init__`` idiom
next to a real bind.
"""
from __future__ import annotations

import ast
from typing import List

from paddle_tpu.analysis.context import (
    STATIC_TENSOR_ATTRS, walk_own,
)
from paddle_tpu.analysis.registry import Finding, register

_SYNC_METHODS = ("numpy", "item", "tolist")
_SYNC_CALLS = {
    "numpy.asarray", "numpy.array", "numpy.copy", "numpy.ascontiguousarray",
    "jax.device_get",
}
_SYNC_BUILTINS = ("float", "int", "bool")

_DOC = __doc__


def _is_const(node: ast.AST) -> bool:
    """Trace-time constants a host conversion of is harmless: literals
    (incl. literal lists/tuples — the `np.asarray([0., 1.])` lookup
    table idiom), len(), and static-metadata attribute chains
    (`int(x.shape[0])`)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.List, ast.Tuple)):
        return all(_is_const(e) for e in node.elts)
    if isinstance(node, ast.UnaryOp):
        return _is_const(node.operand)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id == "len":
        return True
    if isinstance(node, ast.Subscript):
        return _is_const(node.value)
    if isinstance(node, ast.Attribute) and \
            node.attr in STATIC_TENSOR_ATTRS:
        return True
    return False


def _sync_kind(module, call: ast.Call):
    """None, or a short description of the host sync this call performs."""
    func = call.func
    if isinstance(func, ast.Attribute) and func.attr in _SYNC_METHODS:
        return f".{func.attr}()"
    canon = module.canonical(func)
    if canon in _SYNC_CALLS:
        if call.args and _is_const(call.args[0]):
            return None  # converting a trace-time constant is host-safe
        return f"{canon}()"
    if isinstance(func, ast.Name) and func.id in _SYNC_BUILTINS:
        if call.args and not _is_const(call.args[0]):
            return f"{func.id}()"
    return None


def _dispatch_result_events(module, fdef):
    """Per name: binds (assigned from a call to a known jax.jit
    binding) and kills (reassigned from anything else), as sorted
    lineno lists — so a fetch of a REBOUND name isn't flagged."""
    binds, kills = {}, {}

    def target_names(tgt):
        elts = tgt.elts if isinstance(tgt, (ast.Tuple, ast.List)) \
            else [tgt]
        for e in elts:
            if isinstance(e, ast.Starred):
                e = e.value
            if isinstance(e, ast.Name):
                yield e.id

    for node in walk_own(fdef):
        if isinstance(node, ast.Assign):
            is_dispatch = isinstance(node.value, ast.Call) and \
                module.jit_bindings.lookup(node.value.func) is not None
            book = binds if is_dispatch else kills
            for tgt in node.targets:
                for name in target_names(tgt):
                    book.setdefault(name, []).append(node.lineno)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            # `out: jax.Array = self._step_fn(...)` binds like an Assign
            value = getattr(node, "value", None)
            is_dispatch = isinstance(value, ast.Call) and \
                module.jit_bindings.lookup(value.func) is not None
            book = binds if is_dispatch else kills
            for name in target_names(node.target):
                book.setdefault(name, []).append(node.lineno)
        elif isinstance(node, ast.For):
            for name in target_names(node.target):
                kills.setdefault(name, []).append(node.lineno)
    return binds, kills


def _live_bind_line(binds, kills, name, at_line):
    """The dispatch-bind line still governing ``name`` at ``at_line``,
    or None if there is none / a later reassignment killed it."""
    bind = max((b for b in binds.get(name, ()) if b <= at_line),
               default=None)
    if bind is None:
        return None
    if any(bind < k <= at_line for k in kills.get(name, ())):
        return None
    return bind


def _arg_root_name(node: ast.AST):
    """The base Name of ``x``, ``x[i]``, ``x.attr`` argument shapes."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _self_attr_root(node: ast.AST):
    """For ``self.x``, ``self.x[i]``, ``self.x.y`` shapes: the attribute
    read directly off ``self`` (``x``), else None."""
    last = None
    while True:
        if isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Attribute):
            last = node
            node = node.value
        else:
            break
    if isinstance(node, ast.Name) and node.id == "self" and last is not None:
        return last.attr
    return None


def _methods(cdef: ast.ClassDef):
    for node in cdef.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _class_attr_events(module, cdef: ast.ClassDef):
    """Across all direct methods of ``cdef``: ``self`` attributes bound
    to a compiled-dispatch result (directly, or via a local name whose
    dispatch bind is live at the assignment) -> {attr: (method, line)},
    and attributes killed by any other reassignment. ``self.x = None``
    is neither — it's the ``__init__`` placeholder idiom, not a value
    that clears the bind in whichever order methods actually run."""
    binds, kills = {}, {}

    def record(attr, value, lineno, meth, local_binds, local_kills):
        if isinstance(value, ast.Constant) and value.value is None:
            return
        is_dispatch = (
            isinstance(value, ast.Call)
            and module.jit_bindings.lookup(value.func) is not None)
        if not is_dispatch and isinstance(value, ast.Name):
            is_dispatch = _live_bind_line(
                local_binds, local_kills, value.id, lineno) is not None
        if is_dispatch:
            binds.setdefault(attr, (meth.name, lineno))
        else:
            kills.setdefault(attr, (meth.name, lineno))

    for meth in _methods(cdef):
        local_binds, local_kills = _dispatch_result_events(module, meth)
        for node in walk_own(meth):
            if isinstance(node, ast.Assign):
                pairs = []
                tgt = node.targets[0] if len(node.targets) == 1 else None
                if isinstance(tgt, ast.Tuple) \
                        and isinstance(node.value, ast.Tuple) \
                        and len(tgt.elts) == len(node.value.elts):
                    # `self.a, self.b = ka, vb` — track elementwise
                    pairs = list(zip(tgt.elts, node.value.elts))
                else:
                    for t in node.targets:
                        elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                            else [t]
                        pairs.extend((e, node.value) for e in elts)
                for t, value in pairs:
                    if isinstance(t, ast.Starred):
                        t = t.value
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        record(t.attr, value, node.lineno, meth,
                               local_binds, local_kills)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                t = node.target
                value = getattr(node, "value", None)
                if value is not None and isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    if isinstance(node, ast.AugAssign):
                        kills.setdefault(t.attr, (meth.name, node.lineno))
                    else:
                        record(t.attr, value, node.lineno, meth,
                               local_binds, local_kills)
            elif isinstance(node, ast.For):
                t = node.target
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for e in elts:
                    if isinstance(e, ast.Attribute) \
                            and isinstance(e.value, ast.Name) \
                            and e.value.id == "self":
                        kills.setdefault(e.attr, (meth.name, node.lineno))
    return binds, kills


@register(
    "host-sync-in-traced",
    "device->host copy inside a traced function or on a dispatch result",
    _DOC)
def check(module) -> List[Finding]:
    out: List[Finding] = []
    seen = set()
    # placement 1: host conversions inside traced regions
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        kind = _sync_kind(module, node)
        if kind is None:
            continue
        reason = module.trace_reason(node)
        if reason is None:
            continue
        seen.add(id(node))
        out.append(module.finding(
            "host-sync-in-traced", node,
            f"{kind} forces a device->host sync inside a traced "
            f"function ({reason}); compute it in-graph or move it "
            f"outside the traced scope"))
    # placement 2: host fetch of a compiled dispatch's result
    for fdef in module.traces.functions.defs:
        if isinstance(fdef, ast.Lambda):
            continue
        binds, kills = _dispatch_result_events(module, fdef)
        if not binds:
            continue
        for node in walk_own(fdef):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            kind = _sync_kind(module, node)
            if kind is None:
                continue
            # the fetched tensor: the receiver for method spellings
            # (`out.item()`), the first argument otherwise
            if kind.startswith("."):
                target = node.func.value
            elif node.args:
                target = node.args[0]
            else:
                continue
            root = _arg_root_name(target)
            if root is None:
                continue
            bind = _live_bind_line(binds, kills, root, node.lineno)
            if bind is not None:
                seen.add(id(node))
                out.append(module.finding(
                    "host-sync-in-traced", node,
                    f"{kind} fetches '{root}', the result of the "
                    f"compiled dispatch at line {bind} — a "
                    f"per-step device->host copy (the PR-2 copy_frac "
                    f"bug class); keep it on device or fold the "
                    f"consumer into the compiled step"))
    # placement 2b: dispatch results parked on self attributes and
    # fetched from a DIFFERENT method (`self._last = self._step_fn(...)`
    # in step(), `np.asarray(self._last)` in result()). Method call
    # order is unknowable statically, so an attribute reassigned from
    # anything non-dispatch anywhere in the class clears the bind.
    for cdef in ast.walk(module.tree):
        if not isinstance(cdef, ast.ClassDef):
            continue
        attr_binds, attr_kills = _class_attr_events(module, cdef)
        live = {a: b for a, b in attr_binds.items() if a not in attr_kills}
        if not live:
            continue
        for meth in _methods(cdef):
            for node in walk_own(meth):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                kind = _sync_kind(module, node)
                if kind is None:
                    continue
                if kind.startswith("."):
                    target = node.func.value
                elif node.args:
                    target = node.args[0]
                else:
                    continue
                attr = _self_attr_root(target)
                if attr is None or attr not in live:
                    continue
                bind_meth, bind_line = live[attr]
                seen.add(id(node))
                out.append(module.finding(
                    "host-sync-in-traced", node,
                    f"{kind} fetches 'self.{attr}', which carries the "
                    f"compiled-dispatch result bound in "
                    f"{bind_meth}() at line {bind_line} — a cross-method "
                    f"per-step device->host copy; keep it on device or "
                    f"fold the consumer into the compiled step"))
    return out
