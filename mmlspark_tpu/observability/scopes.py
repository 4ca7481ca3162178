"""Which instruction of a compiled program belongs to which
``jax.named_scope``: the table readers join against a device trace.

A device trace names an operation by its HLO instruction (``%fusion.12 =
...``) and XLA names a fusion by its root, so a matmul, a norm and an
optimizer update are one row there. The compiled program knows more: every
instruction, also those inside fused computations, carries ``metadata=
{op_name="jit(step)/loss_and_grad/transpose(jvp(ViT))/block3/mlp/..."}``,
the stack of ``jax.named_scope``s and flax module names it was traced
under, with ``transpose(jvp(...))`` on a backward operation and
``checkpoint/rematted_computation`` on a recomputed one.

:func:`parse` makes the table from a compiled executable: one entry per
instruction of every computation, ``name -> Scope(path, tops)``. ``path``
is the ``op_name`` as the compiler gives it, untouched, and empty for an
instruction without metadata (listed, never left out: a name the trace has
and the table lacks is a fault of the join, not "unscoped"). A fusion takes
the path of the ``convolution`` / ``dot`` inside its fused computation when
it holds one (the product is what takes the time), else its own, else
(without one, or with a bare one the compiler made up: ``reduce_sum``) the
first path with a top-level scope among what it fuses, nested fusions
included. ``tops`` are the top-level scopes (the component after
``jit(...)``) of the instruction and, for a fusion, of everything fused
into it: more than one says the fusion mixes, say, a gradient's product
with its leaf's update.

A program is published as a thunk (:func:`publish`) and parsed when
somebody asks (:func:`table`): asked by nobody, it costs nothing. The
trainer publishes its step program under the hot-span gate
(``DistributedTrainer.step_scopes`` is the same table).

The same executable knows what the program occupies on a device:
:func:`memory` gives its ``memory_analysis()`` in bytes (``argument``,
``output``, ``alias``, ``temp``, ``generated_code``, ``peak_memory``).
One call of the thunk serves both, whichever is asked first; neither the
executable nor a buffer is kept.
"""
from __future__ import annotations

import re
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple


class Scope(NamedTuple):
    path: str                # the instruction's op_name, "" without one
    tops: Tuple[str, ...]    # top-level scopes of it and of what it fuses


Table = Dict[str, Scope]

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_PRODUCTS = ("convolution", "dot")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after `` = ``: the word before
    the operands' parenthesis, past the result's shape (a tuple shape is
    parenthesised and holds spaces, an array's does not)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest[rest.find(" "):]
    rest = rest.lstrip()
    return rest[:rest.find("(")]


def top_scope(path: str) -> str:
    """The component after ``jit(...)``: the outermost scope of the
    program's own (``loss_and_grad``); empty for an empty path."""
    parts = path.split("/")
    return parts[1] if len(parts) > 1 else ""


def parse_text(text: str) -> Table:
    """The table of one HLO module's text (``Compiled.as_text()``)."""
    # computation -> [(instruction, opcode, path, fused computation)]
    bodies: Dict[str, List[Tuple[str, str, str, Optional[str]]]] = {}
    body = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            m = _COMPUTATION.match(line)
            if m is not None:
                body = bodies.setdefault(m.group(1), [])
            continue
        if body is None:
            continue
        name, rest = m.groups()
        opcode = _opcode(rest)
        path = _OP_NAME.search(rest)
        calls = _CALLS.search(rest) if opcode == "fusion" else None
        body.append((name, opcode, path.group(1) if path else "",
                     calls.group(1) if calls else None))

    def fused_into(computation):
        """``(opcode, path)`` of everything fused into a fusion, a nested
        fusion's instructions after the nested fusion itself."""
        for _name, opcode, path, nested in bodies.get(computation, ()):
            yield opcode, path
            if nested:
                yield from fused_into(nested)

    table: Table = {}
    for body in bodies.values():
        for name, _opcode_, path, fused in body:
            inside = list(fused_into(fused)) if fused else ()
            product = next((p for o, p in inside if o in _PRODUCTS and p),
                           None)
            if product:
                path = product
            elif not top_scope(path):
                # no name of its own, or a bare one the compiler made
                # (``reduce_sum``): what it fuses says where it belongs
                path = next((p for _o, p in inside if top_scope(p)), path)
            tops = {top_scope(p) for p in [path] + [p for _o, p in inside]}
            table[name] = Scope(path, tuple(sorted(tops - {""})))
    return table


def parse(compiled: Any) -> Table:
    """The table of a compiled executable (``jit(f).lower(...).compile()``),
    over all its HLO modules."""
    return parse_text(compiled.as_text())


# -- programs, published lazily -------------------------------------------

Memory = Dict[str, int]

_MEMORY_PARTS = ("argument", "output", "alias", "temp", "generated_code")

_lock = threading.Lock()
_thunks: Dict[str, Callable[[], Any]] = {}
# program -> (table, memory) of the one compile its thunk made
_made: Dict[str, Tuple[Table, Optional[Memory]]] = {}


def publish(program: str, compile_thunk: Callable[[], Any]) -> None:
    """Register ``program`` (its name as a device trace shows it:
    ``jit_step``) with a thunk that gives its compiled executable. The
    thunk holds no device buffer and is not called here. A second
    publication under one name takes the first one's place."""
    with _lock:
        _thunks[program] = compile_thunk
        _made.pop(program, None)


def memory_of(compiled: Any) -> Optional[Memory]:
    """A compiled executable's ``memory_analysis()`` as plain bytes, a
    device's share of a sharded program; ``None`` from a backend, or an
    executable, that gives none."""
    analysis = getattr(compiled, "memory_analysis", None)
    stats = analysis() if analysis else None
    if stats is None:
        return None
    found = {part: int(getattr(stats, part + "_size_in_bytes"))
             for part in _MEMORY_PARTS}
    found["peak_memory"] = int(stats.peak_memory_in_bytes)
    return found


def _make(program: str) -> Optional[Tuple[Table, Optional[Memory]]]:
    """``(table, memory)`` of a published program, made on the first call
    (the thunk's compile, a load where the persistent cache holds the
    program, and the parse) and kept; the executable is dropped here."""
    with _lock:
        if program in _made:
            return _made[program]
        thunk = _thunks.get(program)
    if thunk is None:
        return None
    compiled = thunk()
    made = (parse(compiled), memory_of(compiled))
    with _lock:
        if _thunks.get(program) is thunk:
            _made[program] = made
    return made


def table(program: str) -> Optional[Table]:
    """The table of a published program; ``None`` for a name nobody
    published."""
    made = _make(program)
    return made and made[0]


def memory(program: str) -> Optional[Memory]:
    """What a published program occupies on a device, in bytes by part;
    its buffers while it runs are ``argument + output - alias + temp``
    beside its ``generated_code``. ``None`` for a name nobody published."""
    made = _make(program)
    return made and made[1]


def clear() -> None:
    """Forget every published program (tests)."""
    with _lock:
        _thunks.clear()
        _made.clear()
