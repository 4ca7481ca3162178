"""Milliseconds per training step by phase and by part of the model: the
device operations of the step programs in the window, each joined by its
instruction name against the table the program publishes
(``mmlspark_tpu/observability/scopes.py``: name -> the ``jax.named_scope``
path it was traced under), in ``op_ms_per_step``'s unit (every chip's time
over every chip's step programs, one cut by the window's edge counted by
its part inside). ``None`` where the program publishes no table.

The rules, all here, so that the yardstick is the benchmark's:

- *self time*: a ``while``, ``cond`` or ``call`` event covers its
  children, which are events too. Every instant of a chip goes to the
  operation that started last among those running then, so a container
  keeps only what its children leave and the sum over all operations is
  the chip's busy time, once.
- *a child under none of the step's scopes is its container's*: the
  compiler gives some operations no ``op_name`` (zero-fills and weight
  copies inside a routed layer's ``cond``) and those it makes itself a
  bare one (``ragged-dot-none``: the grouped Mosaic products of
  ``ragged_dot``; ``scatter-add``, ``sort``); one that runs inside a
  container counts under the nearest enclosing container whose path
  names a phase. At the top level it stays ``unscoped``.
- *phase*, from the path: ``optimizer`` (under ``optimizer_update``),
  ``ring`` (``metrics_ring``), and under ``loss_and_grad``: ``recompute``
  (``rematted_computation`` in it), ``backward`` (``transpose(`` in it and
  not recomputed), ``forward`` (the rest); ``unscoped`` for an empty path
  or one under none of these.
- *part*: the path is split at ``/``, wrappers (``jvp(x)``,
  ``transpose(jvp(x))``) are taken off each component, and the INNERMOST
  component that is a known name (``PARTS``) decides, so the parts
  partition the step (``mlp`` is not ``mlp_up``); ``other`` without one,
  and for a collective (XLA combines the leaves' all-reduces into one
  operation under one leaf's path).
- *product in a fusion*: XLA labels a fusion by its root; the table gives
  it the path of the matmul fused into it (``scopes.parse``).

A path of several joined by ``;`` (operations XLA merged) counts by its
first. The first call in a run prints one ``# scope_split`` note: the
phase x part table in ms a step, each part by its sub-scope, the time in
fusions that mix ``loss_and_grad`` with ``optimizer_update``, the time
that took its container's path, the operations whose name the table lacks
(there should be none), the largest unscoped operations, and what asking
for the table cost.
"""
import bisect
import re
import time

from benchmark.harness import trace
from benchmark.harness.report import note

PHASES = ("forward", "recompute", "backward", "optimizer", "ring",
          "unscoped")
# part -> the scopes that mean it (``attn`` is a flax module's name: the
# ViT block's attention, inside which no other name lies)
PARTS = {
    "attention": ("mla_attention", "gated_attention", "grouped_attention",
                  "attn"),
    "ffn": ("ffn",),
    "linattn": ("gated_delta_net",),
    "ssm": ("mamba2_mixer",),
    "moe_router": ("moe_router",),
    "moe_dispatch": ("moe_dispatch",),
    "moe_experts": ("moe_experts",),
    "moe_combine": ("moe_combine",),
    "loss": ("lm_loss",),
}
OTHER = "other"
_PART_OF = {name: part for part, names in PARTS.items() for name in names}
_WRAPPER = re.compile(r"^\w+\((.*)\)$")


def _components(path):
    out = []
    for comp in path.split(";")[0].split("/"):
        m = _WRAPPER.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPER.match(comp)
        out.append(comp)
    return out


def phase_of(path):
    comps = _components(path)
    top = comps[1] if len(comps) > 1 else ""
    if top == "optimizer_update":
        return "optimizer"
    if top == "metrics_ring":
        return "ring"
    if top != "loss_and_grad":
        return "unscoped"
    first = path.split(";")[0]
    if "rematted_computation" in first:
        return "recompute"
    return "backward" if "transpose(" in first else "forward"


def part_of(path):
    """``(part, sub)``: the innermost known name's part, and the scope
    right under it (``-`` when the operation lies in the part itself)."""
    comps = _components(path)
    for i in range(len(comps) - 1, -1, -1):
        part = _PART_OF.get(comps[i])
        if part is not None:
            return part, (comps[i + 1] if i + 2 < len(comps) else "-")
    return OTHER, "-"


def self_times(ops):
    """``([(name, ns)], inside)`` of one chip's clipped operations
    ``(name, start, end)``: every instant to the operation that started
    last among those running, so the total is the union of the intervals;
    ``inside[name]`` is the operation that was running, and went on to
    the end of ``name``, when ``name`` started: its container."""
    out, inside, stack, t = [], {}, [], 0

    def give(upto):
        nonlocal t
        name, end = stack[-1]
        upto = min(upto, end)
        if upto > t:
            out.append((name, upto - t))
            t = upto

    for name, start, end in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            give(start)
            stack.pop()
        if stack:
            give(start)
            if stack[-1][1] >= end:
                inside[name] = stack[-1][0]
        t = max(t, start)
        stack.append((name, end))
    while stack:
        give(stack[-1][1])
        stack.pop()
    return out, inside


def _instruction(text):
    """An event's HLO text -> its instruction's name in the table."""
    return text.split(" = ")[0].lstrip("%")


def _table(program):
    try:
        from mmlspark_tpu.observability import scopes
    except ImportError:     # a program from before the table
        return None
    return scopes.table(program)


def _hbm_gb():
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return round(stats.get("bytes_in_use", 0) / 1e9, 3)


def split(events, table, module=r"^jit_step\("):
    """The whole split of one trace against one table."""
    lo, hi = trace.window(events)
    rx = re.compile(module)
    cells, subs, unscoped, missing, labels = {}, {}, {}, {}, {}
    steps = mixed = outside = inherited = 0.0

    def label(path):
        if path not in labels:
            labels[path] = (phase_of(path), *part_of(path))
        return labels[path]

    for dev in events["devices"].values():
        steps += sum(max(0, min(s + d, hi) - max(s, lo)) / d
                     for n, s, d in dev["modules"] if d > 0 and rx.search(n))
        programs = sorted((a, b) for n, a, b in
                          trace._clip(dev["modules"], lo, hi)
                          if rx.search(n))
        starts = [a for a, _b in programs]
        ops = [((name, a), a, b)
               for name, a, b in trace._clip(dev["ops"], lo, hi)]
        segments, inside = self_times(ops)
        for (name, a), ns in segments:
            # an operation is its program's by where it starts
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= programs[i][1]:
                outside += ns
                continue
            entry = table.get(_instruction(name))
            if entry is None:
                missing[name, a] = missing.get((name, a), 0) + ns
            path, tops = entry or ("", ())
            phase, part, sub = label(path)
            up = (name, a)
            while phase == "unscoped" and up in inside:
                up = inside[up]
                phase, part, sub = label(
                    (table.get(_instruction(up[0])) or ("",))[0])
                if phase != "unscoped":
                    inherited += ns
                    sub = trace.op_kind(name)   # in the note by its kind
            if trace.COLLECTIVE.search(_instruction(name)):
                # XLA combines the leaves' reductions into one operation
                # under ONE leaf's path: it is no part's
                part, sub = OTHER, "collective"
            cells[phase, part] = cells.get((phase, part), 0) + ns
            subs[part, sub] = subs.get((part, sub), 0) + ns
            if "loss_and_grad" in tops and "optimizer_update" in tops:
                mixed += ns
            if phase == "unscoped":
                kind = (trace.op_kind(name) + " "
                        + name.partition(" = ")[2].split(" ")[0][:48])
                unscoped[kind] = unscoped.get(kind, 0) + ns
    if not steps:
        return None
    ms = 1e6 * steps
    detail = {}
    for (part, sub), ns in sorted(subs.items(), key=lambda kv: -kv[1]):
        if sub != "-" and len(detail.setdefault(part, {})) < 8:
            detail[part][sub] = round(ns / ms, 3)
    return {
        "steps": round(steps, 3),
        "cells": {k: v / ms for k, v in cells.items()},
        "mixed_ms": mixed / ms, "outside_ms": outside / ms,
        "inherited_ms": inherited / ms,
        "missing": {"count": len(missing),
                    "ms": sum(missing.values()) / ms},
        "detail": detail,
        "unscoped_top": [[k, round(v / ms, 3)] for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:10]]}


def _note(found, program, table_s, hbm):
    parts = sorted({part for _ph, part in found["cells"]})
    by_phase = {ph: {part: round(found["cells"][ph, part], 3)
                     for part in parts if (ph, part) in found["cells"]}
                for ph in PHASES}
    note("scope_split", program=program, steps=found["steps"],
         ms_per_step=by_phase,
         phase_ms={ph: round(sum(row.values()), 3)
                   for ph, row in by_phase.items()},
         part_ms={part: round(sum(v for (_ph, p), v in
                                  found["cells"].items() if p == part), 3)
                  for part in parts},
         by_sub_scope=found["detail"],
         mixed_fusion_ms=round(found["mixed_ms"], 3),
         under_its_containers_path_ms=round(found["inherited_ms"], 3),
         outside_step_programs_ms=round(found["outside_ms"], 3),
         names_the_table_lacks=found["missing"],
         unscoped_top=found["unscoped_top"], table_s=round(table_s, 3),
         hbm_in_use_gb_before_and_after_table=hbm)


def read(rin, phases=None, parts=None, program="jit_step",
         module=r"^jit_step\("):
    """Ms a step in the cells of the phase x part table that ``phases``
    and ``parts`` select (either left out: all of them). A table that is
    there and matches nothing reads 0."""
    if rin.events is None:
        return None
    if not hasattr(rin, "scope_split"):
        before, t = _hbm_gb(), time.perf_counter()
        table = _table(program)
        table_s = time.perf_counter() - t
        rin.scope_split = table and split(rin.events, table, module)
        if rin.scope_split:
            _note(rin.scope_split, program, table_s, [before, _hbm_gb()])
    if not rin.scope_split:
        return None
    return sum(v for (phase, part), v in rin.scope_split["cells"].items()
               if (phases is None or phase in phases)
               and (parts is None or part in parts))
