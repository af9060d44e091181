"""Device regions: which part of the program each device operation of a
compiled step belongs to.

A REGION is a ``jax.named_scope`` of the closed vocabulary
:data:`DEVICE_REGIONS`, declared in the models, the ops, the engine's
sampler and the train step. A scope is compile-time metadata: it rides
every HLO instruction's ``op_name`` and changes no instruction.
:func:`region_map` turns a compiled step's text into ``{HLO instruction
name: where it belongs}``; the profiler's trace names device ops by those
instruction names, so a reader (``Profiler.device_summary(by="region")``,
the benchmark's ``device_regions``) can sum device SELF time by region.

The text costs a second lower + compile of the step (a persistent-cache
hit), so it is made lazily and off the step's path: at its cold dispatch
an owner (the engine, a ``TrainStep``) hands its :class:`StepProgram`
(which holds the jitted callable weakly) the SHAPES it was called with;
while a profiler session is live each dispatch pins the owner, so that
:func:`program_regions`, called after the run by whoever reads the trace,
can still lower the step when the owner's last outside reference is gone.
With no session nothing is pinned and nothing is lowered.
"""
from __future__ import annotations

import re
import threading
import weakref
from typing import Dict, Optional

__all__ = ["DEVICE_REGIONS", "region_map", "program_regions", "StepProgram",
           "self_times"]

# every name passed to jax.named_scope under paddle_tpu/ (held equal by
# tests/test_device_regions.py)
DEVICE_REGIONS = (
    # every step
    "embed", "attn_proj", "mlp", "lm_head", "sampler",
    # attention: the op's two halves, and the layer kinds that wrap them
    "attention", "kv_update", "window_attention", "full_attention",
    "cross_attention", "latent_attention", "sparse_attention",
    "window_latent_attention", "mla_absorb", "attn_gate",
    # state-space layers
    "ssm_proj", "ssm_scan", "ssm_conv", "gmu",
    # gated delta-rule (linear attention) layers
    "gdn_proj", "gdn_conv", "gdn_delta", "gdn_out",
    # recurrent state to and from its snapshots (the prefix cache)
    "state_snapshot", "state_restore",
    # expert layers
    "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
    # the sparse indexer
    "index_proj", "index_scores", "index_select", "index_counts",
    # the train step
    "lm_head_loss", "optimizer",
)

# regions every compiled LM step has since the vocabulary covers the
# step: a text with neither was compiled before that (a stale entry of a
# persistent cache whose key leaves metadata out) and yields no map
_EVERY_STEP = ("embed", "lm_head")

_TRIVIAL = frozenset(("parameter", "tuple", "get-tuple-element", "constant",
                      "bitcast"))
_HEROES = frozenset(("dot", "convolution"))
_INSTR = re.compile(
    r"\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*.+?\s+([\w\-]+)\(")
_COMPUTATION = re.compile(r"(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# what JAX wraps a scope's name in under a transform; jit(f) names a
# function, not a scope
_FUNCTIONS = frozenset(("jit", "pjit"))


def _components(op_name):
    """``op_name`` split at the slashes outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def _unwrap(component):
    """``transpose(jvp(mlp))`` -> ``mlp``; ``jit(f)`` stays what it is."""
    while True:
        m = _WRAPPED.match(component)
        if not m or m.group(1) in _FUNCTIONS:
            return component
        component = m.group(2)


def _placed(op_name):
    """(chain of regions outermost first, backward) of one ``op_name``."""
    if not op_name:
        return (), False
    parts = _components(op_name)
    chain = tuple(c for c in map(_unwrap, parts) if c in DEVICE_REGIONS)
    return chain, any(p.startswith("transpose(") for p in parts)


def _parse(text):
    """{computation: [instruction]} and the entry's name. An instruction
    is a dict: name, opcode, op_name, root, the names after its opcode
    (operands, mostly) and the computations it names by attribute
    (``calls``, ``body``, ...)."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        named = {k: v for k, v in _CALLED.findall(line)}
        branches = _BRANCHES.search(line)
        current.append({
            "name": m.group(2), "opcode": m.group(3),
            "root": bool(m.group(1)), "op_name": op.group(1) if op else "",
            "uses": _OPERAND.findall(line, m.end()), "named": named,
            "branches": [b.strip().lstrip("%") for b in
                         branches.group(1).split(",")] if branches else []})
    return comps, entry


def _reached(instr):
    """The computations whose instructions the device runs one by one
    when it runs ``instr`` (a fusion's are fused: not these)."""
    op, named = instr["opcode"], instr["named"]
    if op == "while":
        return [named[k] for k in ("condition", "body") if k in named]
    if op == "conditional":
        return instr["branches"] + [named[k] for k in (
            "true_computation", "false_computation") if k in named]
    if op == "call":
        return [named["to_apply"]] if "to_apply" in named else []
    if op != "fusion" and "calls" in named:     # async wrappers
        return [named["calls"]]
    return []


def _file_fusion(instr, comps):
    """A fusion is filed by its hero (the first ``dot`` / ``convolution``
    it fuses), else by its root; the regions of its other members are
    kept as ``mixed``."""
    fused = comps.get(instr["named"].get("calls"), ())
    members = [i for i in fused if i["opcode"] not in _TRIVIAL]
    filed = next((i for i in members if i["opcode"] in _HEROES), None) \
        or next((i for i in fused if i["root"] and i["op_name"]), None) \
        or next((i for i in reversed(members) if i["op_name"]), instr)
    op_name = filed["op_name"] or instr["op_name"]
    chain, backward = _placed(op_name)
    own = chain[-1] if chain else None
    others = {c[-1] for c in (_placed(i["op_name"])[0] for i in members)
              if c and c[-1] != own}
    return op_name, chain, backward, tuple(sorted(others))


def _by_neighbour(instrs, placed, orphans):
    """(orphan, place) for every instruction of ``orphans`` that an op
    with a place of its own reads, straight or through other orphans
    (``copy-start`` -> ``copy-done`` -> the fusion that takes the
    prefetched weight): the first such op in schedule order. One that
    nothing placed reads (a result on its way out of the program) goes
    with the last placed op it reads from."""
    users, uses = {}, {}
    for instr in instrs:
        uses[instr["name"]] = instr["uses"]
        for used in instr["uses"]:
            users.setdefault(used, []).append(instr["name"])
    order = {instr["name"]: n for n, instr in enumerate(instrs)}

    def nearest(name, edges):
        todo, seen, found = list(edges.get(name, ())), {name}, []
        while todo:
            other = todo.pop()
            if other in seen or other not in order:
                continue
            seen.add(other)
            if other in placed:
                found.append(other)
            else:
                todo.extend(edges.get(other, ()))
        return found

    for name in orphans:
        readers = nearest(name, users)
        if readers:
            yield name, placed[min(readers, key=order.get)]
            continue
        writers = nearest(name, uses)
        if writers:
            yield name, placed[max(writers, key=order.get)]


def region_map(compiled_text: str) -> Dict[str, dict]:
    """``{HLO instruction name: {"region", "chain", "backward", "mixed",
    "opcode"}}``
    for every instruction of the entry computation and of every
    computation a ``while`` / ``conditional`` / ``call`` reaches: the ops
    the device's ``XLA Ops`` line shows.

    ``region`` is the innermost member of :data:`DEVICE_REGIONS` on the
    instruction's ``op_name`` path (JAX's transforms unwrapped), None
    where the path holds none: nothing is guessed from an op's family.
    ``chain`` is the whole path's regions, outermost first (an op under
    ``sparse_attention/attention`` answers to both). ``backward``: the
    path holds ``transpose(``. A fusion takes all three from its hero,
    the ``dot`` / ``convolution`` it fuses, else from its root, and
    keeps its other members' regions as ``mixed``. An instruction with no
    ``op_name`` at all (the compiler's own: a copy it
    put into a loop's body, a prefetch) belongs to the ``while`` /
    ``conditional`` / ``call`` that runs it or, at the top level, to the
    first op with a place of its own that reads what it made (else the
    last one it reads from); to nothing where there is none. A text in which no
    instruction belongs to ``embed`` or ``lm_head`` predates the
    vocabulary (a stale cache entry): the map is empty, and a reader
    reports nothing rather than a table of unscoped time."""
    comps, entry = _parse(compiled_text)
    if entry is None:
        return {}
    out, todo, seen = {}, [(entry, ((), False))], set()
    while todo:
        comp, outer = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        placed, orphans = {}, []
        for instr in comps.get(comp, ()):
            if instr["opcode"] == "fusion":
                op_name, chain, backward, mixed = _file_fusion(instr, comps)
            else:
                op_name, mixed = instr["op_name"], ()
                chain, backward = _placed(op_name)
            if op_name:
                placed[instr["name"]] = (chain, backward)
            else:
                # the compiler's own (a prefetch, a copy it put into a
                # loop's body): it is part of the loop that runs it ...
                chain, backward = outer
                orphans.append(instr["name"])
            todo.extend((c, (chain, backward)) for c in _reached(instr))
            out[instr["name"]] = {
                "region": chain[-1] if chain else None, "chain": chain,
                "backward": backward, "mixed": mixed,
                "opcode": instr["opcode"]}
        if not outer[0]:
            # ... or, at the top level, of the op it moves data for
            for name, at in _by_neighbour(comps[comp], placed, orphans):
                out[name].update(region=at[0][-1] if at[0] else None,
                                 chain=at[0], backward=at[1])
    if not any(r in v["chain"] for v in out.values() for r in _EVERY_STEP):
        return {}
    return out


# ---------------------------------------------------------------------------
# self time of nested device events (a ``while`` encloses its body's ops)
# ---------------------------------------------------------------------------
def self_times(events):
    """``[(name, start, end)]`` of ONE trace line -> ``[(name, duration,
    self)]``: self is the duration minus the events it encloses, so that
    a ``while``'s own time and its body's ops are each counted once."""
    out, stack = [], []         # stack of [name, end, duration, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, duration, own = stack.pop()
            out.append((name, duration, own))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][3] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a, b - a])
    close(float("inf"))
    return out


# ---------------------------------------------------------------------------
# from a finished run back to its steps' texts
# ---------------------------------------------------------------------------
_lock = threading.Lock()
_programs: Dict[str, "StepProgram"] = {}
_pinned: list = []      # the programs that hold a pin of this session
_seen_live = False      # what the last dispatch that looked saw
_is_enabled = None      # jax.profiler.TraceAnnotation.is_enabled


def _bind_is_enabled():
    global _is_enabled
    from jax.profiler import TraceAnnotation

    _is_enabled = TraceAnnotation.is_enabled
    return _is_enabled


class StepProgram:
    """One compiled step of one owner, known to :func:`program_regions`
    under ``name`` (the newest owner of a name stands for it).

    ``note`` at a cold dispatch, ``dispatched`` at every dispatch: with
    no profiler session live that is one ``is_enabled()`` call."""

    __slots__ = ("name", "_jitted", "_args", "_pin", "_map")

    def __init__(self, name: str, jitted):
        self.name = name
        self._jitted = weakref.ref(jitted)   # its owner keeps it alive
        self._args = self._pin = self._map = None

    def note(self, args):
        """The cold dispatch: keep the call's SHAPES (never an array: a
        step donates its caches) and stand for ``name`` from here on."""
        import jax

        def shape(a):
            if not hasattr(a, "shape") or not hasattr(a, "dtype"):
                return a            # a static argument
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None))

        self._args = jax.tree.map(shape, args)
        self._map = None
        with _lock:
            _programs[self.name] = self

    def dispatched(self, owner):
        """Every dispatch. While a session is live the owner is pinned
        until :func:`program_regions` has read the step or a later
        session starts."""
        global _seen_live
        live = (_is_enabled or _bind_is_enabled())()
        if live is not _seen_live:
            _seen_live = live
            if live:                # a new session: earlier pins go
                with _lock:
                    for prog in _pinned:
                        prog._pin = None
                    del _pinned[:]
        if live and self._pin is None and self._map is None:
            self._pin = owner
            with _lock:
                _pinned.append(self)

    @property
    def pinned(self) -> bool:
        return self._pin is not None

    def regions(self) -> Optional[Dict[str, dict]]:
        """The step's map, lowered and compiled on the first call (None
        where the step is gone: its owner was dropped unpinned)."""
        if self._map is None and self._args is not None:
            jitted = self._jitted()
            if jitted is not None:
                self._map = _compiled_regions(jitted.lower(*self._args))
            self._pin = None
        return self._map


def _compiled_regions(lowered):
    """The map of a lowered step's compiled text. JAX's persistent cache
    keys an executable without its metadata, so the entry it serves may
    be one an older program wrote, same instructions, older scopes; the
    text then shows scopes but none that every step has, and the step is
    compiled once more under a key that sees the metadata (cold once a
    program and cache directory, then served from there). The compiler
    option changes no code and no key: naming one makes JAX ask the
    persistent cache again instead of its in-memory executable."""
    import jax

    text = lowered.compile().as_text()
    placed = region_map(text)
    if placed or not any(f"/{r}/" in text for r in DEVICE_REGIONS):
        return placed
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return region_map(lowered.compile(compiler_options={
            "xla_dump_disable_metadata": False}).as_text())
    finally:
        jax.config.update(flag, was)


def program_regions() -> Dict[str, Dict[str, dict]]:
    """``{program name: region_map}`` of the steps dispatched in this
    process (``"serve.step"``, ``"train.step"``, ...), each lowered on
    the first call that finds it and cached. Never called on a step's
    path: a reader calls it after the run."""
    with _lock:
        programs = list(_programs.values())
    out = {}
    for prog in programs:
        regions = prog.regions()
        if regions is not None:
            out[prog.name] = regions
        else:                       # dropped unpinned: nothing to read
            with _lock:
                if _programs.get(prog.name) is prog:
                    del _programs[prog.name]
    return out
