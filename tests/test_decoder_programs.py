"""The decoder families' programs against what a named commit gave: the
check that a change to ``models/zoo/decoder.py`` or ``parts.py`` which is
meant to move no family's arithmetic has moved none.

Two files under ``tests/data/`` hold, for each of the nine tiny presets,
what THIS module computed on the commit that last remade them (float32,
the CPU backend, every function under ``jax.jit``). PR 53 added the ninth
(``keye_vl2_tiny``: ``sdar_moe``'s block under a causal loss with an
``Indexer`` in every mixer, its program the gradient of ``next_token_loss``
with the indexers' own loss added) after giving ``GroupedAttention`` one
more argument (``indexer``), ``PartsBlock.mix`` a ``stats`` to return
beside ``h`` (a mixer's, or none), ``_remat_block``'s list one more name (``SELECTION``) and
``next_token_loss`` a model's ``aux_loss`` to add: the JSON's diff is ONE
added line, the eight older hashes unmoved, and the eight older presets'
keys of the ``.npz`` are the parent's bit for bit (checked against the
parent's file before it was remade). PR 51 added the eighth
(``kimi_linear_tiny``) after giving ``MlaAttention`` two arguments whose
defaults are GLM's (no query rank, no turn), ``flash_attention`` a second
head width and ``gated_delta_rule`` a decay a key channel, picked from
``g``'s shape: the JSON's diff is ONE added line, the seven older hashes
unmoved, and the seven older presets' keys of the ``.npz`` are the parent's
bit for bit (checked against the parent's file before it was remade). PR
52 put ``KimiDeltaAttention``'s convolution, ``silu`` and L2 norm behind ONE
entry (``linear_attention.conv_silu_norm``; the preset's heads of 8 keep
XLA's form, with q's norm and scale now made right after q's convolution
and not after all three) and remade the JSON: ``kimi_linear_tiny``'s hash
moved (the operations' order), the seven others' did not, and the ``.npz``
came out byte for byte the parent's. PR
47 added the seventh
(``sdar_moe_tiny``: rows of ``[noised | clean]``, its program the gradient
of ``masked_diffusion_loss`` with every noised position weighed 1) after
giving ``parts.rotary`` and ``GroupedAttention`` one more argument each,
and remade both files: the JSON's diff shows the six older hashes unmoved,
and the six older presets' keys of the ``.npz`` are the parent's bit for
bit. PR 48 read the routed layers' weights and group sizes by comparison
(``zoo/moe._at_choice``, ``_slots_by_expert``: no ``gather`` after
``top_k``, no ``bincount``) and remade the JSON: the five routed families'
hashes moved, granite's and olmo's did not, and every key of the ``.npz``
is the parent's bit for bit, the router's and the gate bank's gradients
among them. PR 50 gave ``GroupedAttention``'s q and k heads of 128
channels one Pallas pass for norm and turn
(``ops/pallas_head_norm_turn``) and moved neither file: the presets' heads
have 8 and 16 channels, ``supports`` declines them, and what they lower is
the parent's text, hash for hash and bit for bit. PR 46 put two names on
``_remat_block``'s one list (``MAMBA2_IN``, ``ATTN_QKV``) and remade the
JSON: its diff shows granite's, lfm2's and laguna's hashes moved (their
blocks keep rows they made again before), GLM's and qwen's not (no value
of theirs carries either name), and olmo's moved although it lets
``ATTN_QKV`` go: its text differs from the parent's ONLY in the numbers
jax appends to private functions' names (``@_where_178`` ->
``@_where_179``: one more function is traced and none more is called;
with the symbols renamed in order of appearance the two texts are equal).
The ``.npz`` did not move: every key is the parent's bit for bit, the
gradients too (PR 45, which moved GLM's, qwen's and lfm2's positions to
``parts.rotary``'s product, made it; granite's, olmo's and laguna's
outputs are commit 79fac30's, PR 42, and PR 44's):

- ``decoder_parent_outputs.npz``: the logits; the ``hidden=True`` outputs
  the chunked loss reads (``hidden``, GLM's ``mtp_hidden``, every key of
  ``stats``); for the routed families the gradient of ``mean(logits^2)``
  at ``block1/ffn``'s router and gate bank;
- ``decoder_parent_programs.json``: the sha256 of the lowered text of the
  gradient of ``next_token_loss`` through ``hidden=True``. The text
  carries no debug information, so neither a class name nor a line number
  is in it: it moves when an operation, a shape or their order moves.

A PR that changes a family's program on purpose remakes both with

    JAX_PLATFORMS=cpu python tests/test_decoder_programs.py --write

(without ``--write`` it prints the nine hashes and writes nothing), names
its own commit here, and its diff of the JSON then shows which families it
touched and which it did not.
"""
import hashlib
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    import conftest  # noqa: F401  (the tests' backend, before jax starts)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.train.lm_loss import (  # noqa: E402
    masked_diffusion_loss, next_token_loss)

PRESETS = ("glm4_moe_lite_tiny", "qwen3_next_tiny", "granite_hybrid_tiny",
           "olmo_hybrid_tiny", "lfm2_moe_tiny", "laguna_tiny",
           "sdar_moe_tiny", "kimi_linear_tiny", "keye_vl2_tiny")
DIFFUSION = ("sdar_moe_tiny",)
DATA = Path(__file__).resolve().parent / "data"
OUTPUTS = DATA / "decoder_parent_outputs.npz"
PROGRAMS = DATA / "decoder_parent_programs.json"


def _built(preset):
    """The preset's module, its seeded parameters and two rows of tokens."""
    module = build_model(preset)["module"]
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 96, size=(2, 24)).astype(np.int32))
    return module, jax.jit(module.init)(jax.random.PRNGKey(3), tokens), tokens


def _head_kernel(params):
    """``(dim, vocab)``: the untied head's, or the one table transposed."""
    p = params["params"]
    return p["lm_head"]["kernel"] if "lm_head" in p \
        else p["token_embedding"]["embedding"].T


def _outputs(module, params, tokens):
    """What the ``.npz`` holds of one preset, by key."""
    @jax.jit
    def run(params):
        out = module.apply(params, tokens, hidden=True)
        got = {"logits": module.apply(params, tokens),
               **{k: v for k, v in out.items() if k != "stats"},
               **{f"stats.{k}": v for k, v in out["stats"].items()}}
        if "router" in params["params"]["block1"]["ffn"]:
            g = jax.grad(lambda p: jnp.mean(jnp.square(module.apply(
                p, tokens))))(params)["params"]["block1"]["ffn"]
            got.update(router=g["router"]["kernel"],
                       experts_gate=g["experts_gate"])
        return got
    return {k: np.asarray(v) for k, v in run(params).items()}


def _program(module, params, tokens, diffusion=False):
    """sha256 of the lowered loss gradient: traced, never compiled. With
    ``diffusion`` the rows are ``[noised | clean]`` and the loss the
    masked-diffusion one, every noised position weighed 1."""
    def loss(params, tokens):
        out = module.apply(params, tokens, hidden=True)
        if diffusion:
            clean = tokens[:, tokens.shape[1] // 2:]
            return masked_diffusion_loss(
                out, _head_kernel(params), clean, jnp.ones(clean.shape),
                chunk=16, dtype=jnp.float32)[0]
        return next_token_loss(out, _head_kernel(params), tokens, chunk=16,
                               dtype=jnp.float32)[0]
    text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.fixture(scope="module", params=PRESETS)
def built(request):
    return (request.param,) + _built(request.param)


def test_the_families_that_share_parts_give_their_parents_outputs(built):
    """Every family's arithmetic is the named commit's BIT FOR BIT (the
    module's docstring: what the file holds and the command that remakes
    it). The parameters come from the module's own ``init``, so the tree's
    paths and the order of its draws are held too."""
    preset, module, params, tokens = built
    golden = np.load(OUTPUTS)
    want = {k.split(".", 1)[1]: golden[k] for k in golden.files
            if k.startswith(preset + ".")}
    got = _outputs(module, params, tokens)
    assert set(got) == set(want) and "hidden" in got
    for k in sorted(got):
        assert got[k].dtype == want[k].dtype, k
        assert np.array_equal(got[k], want[k]), k


def test_the_loss_gradient_lowers_to_its_parents_program(built):
    """The step the cells run (the chunked loss over ``hidden=True``, its
    gradient) lowers to the text the named commit gave, operation for
    operation."""
    preset, module, params, tokens = built
    with open(PROGRAMS) as f:
        want = json.load(f)
    assert set(want) == set(PRESETS)
    assert _program(module, params, tokens, preset in DIFFUSION) \
        == want[preset], preset


def main(write: bool) -> None:
    outputs, programs = {}, {}
    for preset in PRESETS:
        built = _built(preset)
        programs[preset] = _program(*built, preset in DIFFUSION)
        print(preset, programs[preset], flush=True)
        outputs.update({f"{preset}.{k}": v
                        for k, v in _outputs(*built).items()})
    if write:
        np.savez_compressed(OUTPUTS, **outputs)
        with open(PROGRAMS, "w") as f:
            json.dump(programs, f, indent=1)
            f.write("\n")
        print("wrote", OUTPUTS.name, sorted(outputs), "and", PROGRAMS.name)


if __name__ == "__main__":
    main("--write" in sys.argv[1:])
