"""What the step program occupies on a chip, in GB (10^9 bytes): the
``memory_analysis()`` of the executable the program's scope table is made
from (``mmlspark_tpu/observability/scopes.py``: ``memory(program)``, the
same one compile after the window as ``scope_ms_per_step``'s table),
``argument + output - alias + temp + generated_code``: its buffers while
it runs and its code, a chip's share of a sharded program. That is the
step's own mark, where ``device.hbm_peak_gb.train`` reads the allocator's
peak over the whole run, set-up included. ``None`` where the program
publishes no such numbers. The first call prints a ``# step_memory`` note
with the parts."""
from benchmark.harness.report import note

_PARTS = ("argument", "output", "alias", "temp", "generated_code")


def _memory(program):
    try:
        from mmlspark_tpu.observability import scopes
    except ImportError:
        return None
    memory = getattr(scopes, "memory", None)    # a program from before it
    return memory(program) if memory else None


def step_bytes(found):
    return (found["argument"] + found["output"] - found["alias"]
            + found["temp"] + found["generated_code"])


def read(rin, program="jit_step"):
    if not hasattr(rin, "step_memory"):
        rin.step_memory = _memory(program)
        if rin.step_memory:
            note("step_memory", program=program,
                 gb=round(step_bytes(rin.step_memory) / 1e9, 3),
                 parts_gb={k: round(rin.step_memory[k] / 1e9, 3)
                           for k in _PARTS + ("peak_memory",)})
    if not rin.step_memory:
        return None
    return step_bytes(rin.step_memory) / 1e9
