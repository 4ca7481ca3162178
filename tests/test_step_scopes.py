"""The step program's scope table (``observability/scopes.py``): which
instruction of the compiled step belongs to which ``jax.named_scope``.

Covered here: the parse on a hand-written module (every instruction listed,
paths untouched, a fusion under its product's path with every top scope it
holds, one with no scope of its own under what it fuses, no metadata = an
empty path, never left out); the table of a real
``DistributedTrainer`` step with a rematted flax block (a forward, a
recomputed, a backward and an ``optimizer_update`` path each occur, ``ffn``
is the innermost name on the feed-forward part's products); the gate
(nothing published with it off; on and nobody asking, the step is traced
and compiled once); and the one helper ``step_scopes()`` and
``_estimate_flops`` both compile through.
"""
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from mmlspark_tpu.models.zoo.parts import RMSNorm, SwiGluMlp
from mmlspark_tpu.observability import scopes
from mmlspark_tpu.parallel.mesh import mesh_from_config
from mmlspark_tpu.parallel.trainer import DistributedTrainer
from mmlspark_tpu.utils import config

PRODUCT = ("jit(step)/loss_and_grad/transpose(jvp(Tiny))/block0/ffn/"
           "mlp_up/dot_general")
UPDATE = "jit(step)/optimizer_update/add"
MODULE = f"""HloModule jit_step, is_scheduled=true, entry_computation_layout={{()->f32[]}}

%region_0.1 (a.1: f32[], b.1: f32[]) -> f32[] {{
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a.1, %b.1), metadata={{op_name="jit(step)/loss_and_grad/jvp(Tiny)/reduce_sum" stack_frame_id=3}}
}}

%fused_computation.2 (param_0.1: f32[8,4], param_1.2: f32[4,4]) -> f32[8,4] {{
  %param_0.1 = f32[8,4]{{1,0}} parameter(0)
  %param_1.2 = f32[4,4]{{1,0}} parameter(1)
  %convolution.3 = f32[8,4]{{1,0}} convolution(%param_0.1, %param_1.2), dim_labels=bf_io->bf, metadata={{op_name="{PRODUCT}" stack_frame_id=7}}
  ROOT %add.4 = f32[8,4]{{1,0}} add(%convolution.3, %param_0.1), metadata={{op_name="{UPDATE}"}}
}}

%fused_computation.8 (param_0.3: f32[8,4]) -> f32[8,4] {{
  %param_0.3 = f32[8,4]{{1,0}} parameter(0)
  ROOT %exp.5 = f32[8,4]{{1,0}} exponential(%param_0.3), metadata={{op_name="jit(step)/loss_and_grad/jvp(Tiny)/block0/exp"}}
}}

%fused_computation.9 (param_0.4: f32[8,4]) -> f32[8,4] {{
  %param_0.4 = f32[8,4]{{1,0}} parameter(0)
  ROOT %fusion.6 = f32[8,4]{{1,0}} fusion(%param_0.4), kind=kLoop, calls=%fused_computation.8
}}

%body.5 (arg.1: (s32[], f32[8,4])) -> (s32[], /*index=1*/f32[8,4]) {{
  %arg.1 = (s32[], f32[8,4]{{1,0}}) parameter(0)
  %gte.1 = f32[8,4]{{1,0}} get-tuple-element(%arg.1), index=1
  ROOT %tuple.2 = (s32[], f32[8,4]{{1,0}}) tuple(%gte.1, %gte.1)
}}

ENTRY %main.7 (p0: f32[8,4], p1: f32[4,4]) -> f32[8,4] {{
  %p0 = f32[8,4]{{1,0:T(8,128)}} parameter(0)
  %p1 = f32[4,4]{{1,0}} parameter(1)
  %add_fusion = f32[8,4]{{1,0:T(8,128)S(1)}} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="{UPDATE}"}}
  %while.6 = (s32[], /*index=1*/f32[8,4]{{1,0}}) while(%tuple.0), condition=%cond.5, body=%body.5, metadata={{op_name="jit(step)/loss_and_grad/jvp(Tiny)/block0/checkpoint/rematted_computation/scan/while"}}
  %copy.8 = f32[8,4]{{0,1}} copy(%add_fusion)
  %fusion.7 = f32[8,4]{{1,0}} fusion(%copy.8), kind=kCustom, calls=%fused_computation.9
  %reduce_fusion = f32[8,4]{{1,0}} fusion(%copy.8), kind=kLoop, calls=%fused_computation.8, metadata={{op_name="reduce_sum"}}
  ROOT %neg-thing_1.2 = f32[8,4]{{1,0}} negate(%copy.8), metadata={{op_name="a;b"}}
}}
"""


# ------------------------------------------------------ the parse, by hand
def test_every_instruction_of_every_computation_is_listed():
    table = scopes.parse_text(MODULE)
    assert set(table) == {
        "a.1", "b.1", "add.9", "param_0.1", "param_1.2", "convolution.3",
        "add.4", "arg.1", "gte.1", "tuple.2", "p0", "p1", "add_fusion",
        "while.6", "copy.8", "neg-thing_1.2", "param_0.3", "exp.5",
        "param_0.4", "fusion.6", "fusion.7", "reduce_fusion"}


def test_a_path_is_kept_as_the_compiler_gives_it():
    table = scopes.parse_text(MODULE)
    assert table["while.6"] == scopes.Scope(
        "jit(step)/loss_and_grad/jvp(Tiny)/block0/checkpoint/"
        "rematted_computation/scan/while", ("loss_and_grad",))
    assert table["neg-thing_1.2"].path == "a;b"


def test_an_instruction_without_metadata_has_an_empty_path():
    table = scopes.parse_text(MODULE)
    for name in ("copy.8", "p0", "gte.1", "tuple.2"):
        assert table[name] == scopes.Scope("", ())


def test_a_fusion_takes_its_products_path_and_keeps_every_top_scope():
    table = scopes.parse_text(MODULE)
    assert table["add_fusion"] == scopes.Scope(
        PRODUCT, ("loss_and_grad", "optimizer_update"))
    # the fused instructions themselves keep their own
    assert table["add.4"].path == UPDATE
    assert table["convolution.3"].tops == ("loss_and_grad",)


def test_a_fusion_with_no_scope_of_its_own_takes_what_it_fuses():
    table = scopes.parse_text(MODULE)
    inner = "jit(step)/loss_and_grad/jvp(Tiny)/block0/exp"
    # through a nested fusion; and in place of a bare name of XLA's
    for name in ("fusion.7", "fusion.6", "reduce_fusion"):
        assert table[name] == scopes.Scope(inner, ("loss_and_grad",)), name


@pytest.mark.parametrize("rest, opcode", [
    ("f32[] add(%a, %b)", "add"),
    ("f32[8,4]{1,0:T(8,128)S(1)} fusion(%p0), kind=kLoop", "fusion"),
    ("(s32[], /*index=1*/f32[8,4]{1,0:T(8,128)(2,1)}) while(%t)", "while"),
    ("((f32[2], s32[]), pred[]) conditional(%p, %a, %b)", "conditional"),
    ("bf16[1,2]{1,0} custom-call(%a), custom_call_target=\"x\"",
     "custom-call"),
])
def test_the_opcode_is_found_past_the_shape(rest, opcode):
    assert scopes._opcode(rest) == opcode


def test_top_scope():
    assert scopes.top_scope(UPDATE) == "optimizer_update"
    assert scopes.top_scope("") == "" and scopes.top_scope("jit(f)") == ""


# -------------------------------------------------- programs, lazily
class _Compiled:
    def as_text(self):
        return MODULE


@pytest.fixture
def registry():
    scopes.clear()
    try:
        yield scopes
    finally:
        scopes.clear()


def test_a_published_program_is_compiled_and_parsed_when_asked_and_once(
        registry):
    calls = []

    def thunk():
        calls.append(1)
        return _Compiled()

    scopes.publish("jit_f", thunk)
    assert not calls
    first = scopes.table("jit_f")
    assert first["add_fusion"].path == PRODUCT and len(calls) == 1
    assert scopes.table("jit_f") is first and len(calls) == 1
    assert scopes.table("jit_g") is None


def test_a_second_publication_takes_the_firsts_place(registry):
    scopes.publish("jit_f", _Compiled)
    assert scopes.table("jit_f")
    scopes.publish("jit_f", lambda: type("C", (), {
        "as_text": lambda self: ""})())
    assert scopes.table("jit_f") == {}


# ------------------------------------------------ a real trainer's step
class Block(nn.Module):
    @nn.compact
    def __call__(self, x):
        return x + SwiGluMlp(16, 32, jnp.float32, name="ffn")(
            RMSNorm(name="norm")(x))


class Tiny(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(16, name="embed")(x)
        for i in range(2):
            x = nn.remat(Block)(name=f"block{i}")(x)
        return nn.Dense(1, name="head")(x)[..., 0]


def _trainer():
    module = Tiny()
    x = jnp.ones((8, 4), jnp.float32)

    def loss_fn(params, batch, rng):
        return jnp.mean((module.apply(params, batch["x"]) - batch["y"]) ** 2)

    trainer = DistributedTrainer(
        loss_fn, optax.adamw(1e-2),
        mesh=mesh_from_config(jax.devices()[:1]))
    state = trainer.init(lambda: module.init(jax.random.PRNGKey(0), x))
    return trainer, state, {"x": x, "y": jnp.zeros((8,), jnp.float32)}


@pytest.fixture
def annotate():
    config.set("observability.annotate", True)
    try:
        yield
    finally:
        config.unset("observability.annotate")


@pytest.fixture
def stepped(registry, annotate):
    trainer, state, batch = _trainer()
    state, _ = trainer.train_step(state, batch, jax.random.PRNGKey(1))
    return trainer, state, batch


def test_the_table_lists_every_instruction_of_the_compiled_step(stepped):
    trainer, state, batch = stepped
    table = trainer.step_scopes()
    assert table is scopes.table("jit_step")
    fn = trainer._train_steps[False]
    text = trainer._compile_step(
        fn, state, batch, jax.random.PRNGKey(1)).as_text()
    names = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text, re.M)
    assert len(names) > 100 and set(names) == set(table)
    assert text.startswith("HloModule jit_step")


@pytest.mark.parametrize("phase, has, lacks", [
    ("forward", ("/loss_and_grad/jvp(Tiny)/",),
     ("transpose(", "rematted_computation")),
    ("recomputed", ("/loss_and_grad/", "rematted_computation"), ()),
    ("backward", ("/loss_and_grad/transpose(jvp(Tiny))/",),
     ("rematted_computation",)),
    ("optimizer", ("jit(step)/optimizer_update/",), ()),
    ("ring", ("jit(step)/metrics_ring/",), ()),
])
def test_each_phase_of_the_step_has_a_path(stepped, phase, has, lacks):
    paths = [s.path for s in stepped[0].step_scopes().values()]
    assert any(all(h in p for h in has) and not any(l in p for l in lacks)
               for p in paths), phase


def test_ffn_is_the_innermost_name_on_the_feed_forward_products(stepped):
    table = stepped[0].step_scopes()
    dots = [s.path for s in table.values()
            if s.path.endswith("dot_general") and "/mlp_" in s.path]
    # gate, up and down of two blocks; forward, recomputed and backward
    assert len(dots) >= 6
    for path in dots:
        assert re.search(r"/ffn/mlp_(gate|up|down)/dot_general$", path), path
    assert {"recomputed" if "rematted_computation" in p else
            "backward" if "transpose(" in p else "forward"
            for p in dots} == {"forward", "recomputed", "backward"}


def test_with_the_gate_off_nothing_is_published(registry):
    trainer, state, batch = _trainer()
    state, _ = trainer.train_step(state, batch, jax.random.PRNGKey(1))
    assert scopes.table("jit_step") is None
    assert trainer.step_scopes() is None and not trainer._scoped


def test_gate_on_and_nobody_asking_the_step_is_compiled_once(registry):
    # as tests/test_hot_spans.py counts it: every compile-path event of
    # jax's, not backend compiles alone
    seen = []

    def listener(name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            seen.append(name)

    def steps():
        start = len(seen)
        trainer, state, batch = _trainer()
        for _ in range(3):
            state, _m = trainer.train_step(state, batch,
                                           jax.random.PRNGKey(1))
        return sorted(seen[start:])

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        steps()                          # helper programs compile here
        off = steps()
        config.set("observability.annotate", True)
        try:
            on = steps()
        finally:
            config.unset("observability.annotate")
        quiet = len(seen)
        assert scopes.table("jit_step")             # asking is what costs
        assert len(seen) > quiet
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "/jax/core/compile/backend_compile_duration" in off
    assert on == off


def test_step_scopes_and_estimate_flops_compile_through_one_helper(
        stepped, monkeypatch):
    trainer, state, batch = stepped
    real, calls = trainer._compile_step, []

    def counted(fn, *args):
        calls.append(fn)
        return real(fn, *args)

    monkeypatch.setattr(trainer, "_compile_step", counted)
    assert trainer._estimate_flops(state, batch, jax.random.PRNGKey(1)) > 0
    assert trainer.step_scopes()
    assert calls == [trainer._train_steps[False]] * 2
    trainer.step_scopes()                       # kept: no third compile
    assert len(calls) == 2


def test_the_published_thunk_keeps_shapes_and_no_buffer(stepped):
    trainer, state, batch = stepped
    thunk = scopes._thunks["jit_step"]
    held = [c.cell_contents for c in thunk.__closure__]
    leaves = jax.tree_util.tree_leaves(
        [h for h in held if isinstance(h, (tuple, dict, list))])
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          for x in leaves)


# ------------------------------------------- the step program's bytes
class _Stats:
    generated_code_size_in_bytes = 5
    argument_size_in_bytes = 400
    output_size_in_bytes = 300
    alias_size_in_bytes = 200
    temp_size_in_bytes = 100
    peak_memory_in_bytes = 650


class _Measured(_Compiled):
    def memory_analysis(self):
        return _Stats()


@pytest.mark.parametrize("order", [("table", "memory"), ("memory", "table"),
                                   ("memory", "memory", "table")])
def test_memory_and_table_share_one_thunk_call_and_keep_no_executable(
        registry, order):
    import gc
    import weakref
    made = []

    def thunk():
        compiled = _Measured()
        made.append(weakref.ref(compiled))
        return compiled

    scopes.publish("jit_f", thunk)
    got = {ask: getattr(scopes, ask)("jit_f") for ask in order}
    assert len(made) == 1                           # whichever came first
    assert got["table"]["add_fusion"].path == PRODUCT
    assert got["memory"] == {"argument": 400, "output": 300, "alias": 200,
                             "temp": 100, "generated_code": 5,
                             "peak_memory": 650}
    assert scopes.memory("jit_f") is got["memory"]
    gc.collect()
    assert made[0]() is None                        # the executable went
    assert scopes.memory("jit_g") is None


def test_an_executable_without_an_analysis_has_a_table_and_no_memory(
        registry):
    scopes.publish("jit_f", _Compiled)
    assert scopes.memory("jit_f") is None and scopes.table("jit_f")
    scopes.publish("jit_f", _Measured)              # a second publication
    assert scopes.memory("jit_f")["temp"] == 100


def test_step_memory_is_the_compiled_steps_own_analysis(stepped,
                                                        monkeypatch):
    trainer, state, batch = stepped
    real, calls = trainer._compile_step, []

    def counted(fn, *args):
        calls.append(fn)
        return real(fn, *args)

    monkeypatch.setattr(trainer, "_compile_step", counted)
    memory = trainer.step_memory()
    assert trainer.step_scopes() and len(calls) == 1    # one compile, both
    assert memory is scopes.memory("jit_step")
    stats = real(trainer._train_steps[False], state, batch,
                 jax.random.PRNGKey(1)).memory_analysis()
    assert memory["argument"] == stats.argument_size_in_bytes > 0
    assert memory["temp"] == stats.temp_size_in_bytes
    assert memory["alias"] == stats.alias_size_in_bytes > 0   # the donation
    assert set(memory) == {"argument", "output", "alias", "temp",
                           "generated_code", "peak_memory"}
    assert all(isinstance(v, int) for v in memory.values())


def test_step_memory_is_none_until_a_step_is_published(registry):
    trainer, state, batch = _trainer()
    assert trainer.step_memory() is None
    trainer.train_step(state, batch, jax.random.PRNGKey(1))   # gate off
    assert trainer.step_memory() is None
