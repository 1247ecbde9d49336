"""What the program compiled, and what each instruction of it was for.

A profiler trace names a device op by its instruction's text without
``metadata=``, so a ``jax.named_scope`` never reaches a trace's reader.
It does reach the compiled program: ``Compiled.as_text()`` gives every
instruction its ``op_name``, and jax writes the transformation into it
unasked (``jit(train_step_lm)/transpose(jvp(ffn))/dot_general``).  So the
step makers (``parallel/train.py``) jit through :func:`named_jit`, which
remembers what ``.lower(...).compile()`` returned under the program's
module name, ONE program a name, the newest; and :func:`scopes` joins a
trace's instruction names to phases and scopes, on demand.

Nothing is parsed and no clock is read when a program is compiled or
called: remembering is one dictionary store a compile, the call path is
``jax.jit``'s own object.  No switch.  A program that is only ever
CALLED (the serving engine's) never passes ``.lower().compile()`` and is
not remembered.

jax's compile cache leaves metadata out of its key: an executable cached
by a tree without a scope comes back for a tree with it, under the old
``op_name``s (docs/metrics.md, "Device time by phase").
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, Dict, List, NamedTuple, Tuple

PHASES = ("forward", "recompute", "backward", "reduce", "optimizer", "other")
REDUCE_SCOPES = ("grad_reduce", "stats_reduce")
_MATMULS = ("convolution", "dot", "custom-call")

_compiled: Dict[str, Any] = {}       # module name -> jax.stages.Compiled


class _Lowered:
    """``jax.stages.Lowered`` whose ``compile`` is remembered."""

    def __init__(self, lowered, module: str):
        self._lowered, self._module = lowered, module

    def compile(self, *args, **kwargs):
        compiled = self._lowered.compile(*args, **kwargs)
        _compiled[self._module] = compiled
        return compiled

    def __getattr__(self, name):
        return getattr(self._lowered, name)


def named_jit(fn: Callable, name: str, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` as a program called ``name`` (module
    ``jit_<name>``: jax names a program after its function) whose
    ``.lower(...).compile()`` is remembered for :func:`scopes`.  What
    comes back IS the ``jax.jit`` object; ``compile`` hands back jax's own
    ``Compiled``."""
    import jax

    @functools.wraps(fn)        # the arguments' names are the parameters'
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    jitted = jax.jit(program, **jit_kwargs)
    lower = jitted.lower
    jitted.lower = lambda *a, **k: _Lowered(lower(*a, **k), f"jit_{name}")
    return jitted


def remembered() -> Tuple[str, ...]:
    return tuple(_compiled)


def forget() -> None:
    _compiled.clear()


def phase(op_name: str) -> str:
    """One of ``PHASES``; the first rule that matches."""
    parts = op_name.split("/")
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if any(p in REDUCE_SCOPES for p in parts):
        return "reduce"
    if "optimizer" in parts:
        return "optimizer"
    return "forward" if "jvp(" in op_name else "other"


_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap|pmap)\((.*)\)$")
_NOT_A_SCOPE = re.compile(
    r"^p?jit\(|->|^(?:shard_map|while|body|cond|branch_\d+(?:_fun)?|checkpoint"
    r"|remat2?|closed_call|rematted_computation|custom_[jv][jv]p_call\w*)$")


def scope(op_name: str, depth: int = 2) -> str:
    """The named scopes of ``op_name``, outermost first, at most ``depth``
    of them (``stage3/norm``): the path without the trailing primitive,
    the transformations' wrappers (``transpose(jvp(ffn))`` is ``ffn``),
    jax's own functions (``jit(relu)``, an einsum's ``bsd,df->bsf``) and
    the control flow's elements.  ``""`` where no scope is left."""
    out: List[str] = []
    for p in op_name.split("/")[:-1]:
        while (m := _WRAPPED.match(p)):
            p = m.group(1)
        # ``transpose(jvp(a))/jvp(a)`` names ``a`` twice: once is enough.
        if p and not _NOT_A_SCOPE.search(p) and out[-1:] != [p]:
            out.append(p)
    return "/".join(out[:depth])


class Scope(NamedTuple):
    op_name: str        # of the instruction that decides the phase
    phase: str
    mixed: bool         # a fusion whose inner instructions' phases differ


class _Ins(NamedTuple):
    name: str
    opcode: str
    op_name: str
    called: Tuple[str, ...]     # computations it calls, fused or walked
    root: bool


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=\s*.*?\s"
                          r"([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([^\s,}]+)"
                     r"|branch_computations=\{([^}]*)\}")
_WALKED = ("while", "conditional", "call")


def parse(text: str) -> Dict[str, Scope]:
    """:func:`scopes` of an optimized HLO module's text."""
    comps: Dict[str, List[_Ins]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        if (m := _INSTRUCTION.match(line)) and cur is not None:
            op = _OP_NAME.search(line)
            called = tuple(
                c.strip().lstrip("%") for one, branches in
                _CALLED.findall(line) for c in (one or branches).split(","))
            cur.append(_Ins(m.group(2), m.group(3), op.group(1) if op else "",
                            called, bool(m.group(1))))
        elif (m := _COMPUTATION.match(line)):
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
    out: Dict[str, Scope] = {}

    def fused(ins):             # a fusion's instructions, nested ones too
        for c in ins.called:
            for i in comps.get(c, ()):
                yield i
                if i.opcode == "fusion":
                    yield from fused(i)

    def walk(comp):
        for ins in comps.get(comp, ()):
            op_name, mixed = ins.op_name, False
            if ins.opcode == "fusion":
                body = [i for i in fused(ins) if i.op_name]
                first = [i for i in body if i.opcode in _MATMULS] or (
                    [ins] if op_name else [i for i in body if i.root]
                    or body[-1:])
                op_name = first[0].op_name if first else ""
                mixed = len({phase(i.op_name) for i in body} - {"other"}) > 1
            out[ins.name] = Scope(op_name, phase(op_name), mixed)
            if ins.opcode in _WALKED:
                for c in ins.called:
                    walk(c)

    walk(entry)
    return out if any(s.op_name for s in out.values()) else {}


def scopes(module_name: str) -> Dict[str, Scope]:
    """``{instruction name: Scope}`` of the program remembered under
    ``module_name`` (``jit_train_step_lm``): every instruction of the entry
    computation and of ``while`` / ``conditional`` / ``call`` bodies; a
    fusion takes the phase of its one ``convolution`` / ``dot`` /
    ``custom-call`` where it has one, else its own (its root's), and is
    ``mixed`` where its inner instructions' phases differ.  Parsed when
    asked for (the LM step's text is megabytes).  ``{}`` where nothing of
    that name was remembered or its text holds no ``op_name``."""
    compiled = _compiled.get(module_name)
    return parse(compiled.as_text()) if compiled is not None else {}
