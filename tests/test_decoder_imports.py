"""The nine decoders share their parts through ``models/parts.py``,
``models/mamba2.py``, ``models/latent.py``, ``models/experts.py`` and
``models/loss.py`` and never through one another
(ROADMAP D13); the names the benchmark calls are where ``PERF.md`` §3 says;
and the helpers of ``models/parts.py`` that build parameters left every
leaf of the toy models where the parent of PR 42 had it."""

import ast
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "horovod_tpu", "models")
DECODERS = ("olmoe", "olmo_hybrid", "smallthinker", "sdar", "granite",
            "kimi_linear", "nemotron_h", "joyai_flash", "lfm2")


def imported_modules(path):
    """Every module ``path`` imports, at its top level or inside a
    function, as ``from`` names it (``.parts``, ``..ops.attention``) or as
    ``import`` does, and every name taken from one as if it were a module
    too (``from . import x`` gives ``.x``)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            origin = "." * node.level + (node.module or "")
            found.add(origin)
            found.update(origin + "." * bool(node.module) + alias.name
                         for alias in node.names)
    return found


def names_a_decoder(module: str) -> bool:
    return module.split(".")[-1] in DECODERS and (
        module.startswith(".") or module.startswith("horovod_tpu.models"))


@pytest.mark.parametrize("name", DECODERS + ("parts", "mamba2", "latent",
                                             "experts", "loss"))
def test_no_shared_module_and_no_decoder_imports_a_decoder(name):
    imports = imported_modules(os.path.join(MODELS, name + ".py"))
    assert imports, name
    assert not sorted(filter(names_a_decoder, imports))


def surface():
    """``module.attribute`` for every name of the paragraph of ``PERF.md``
    that lists what the benchmark calls: ``hvd.a / b``, ``hvd.C.{x,y,...}``
    and ``models.m.{A, B}`` written out."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    paragraph = text[text.index("**The surface the benchmark calls"):]
    paragraph = paragraph[:paragraph.index("\n\n")]
    names = []
    for item in re.findall(r"`([^`]+)`", paragraph):
        item = re.sub(r"\([^)]*\)", "", item).strip()
        match = re.fullmatch(r"((?:hvd|models)[\w.]*)\.\{([^}]*)\}", item)
        if match:
            prefix, members = match.group(1), match.group(2).split(",")
        elif item.startswith(("hvd.", "models.")):
            prefix, _, first = item.split(" / ")[0].rpartition(".")
            members = [first] + item.split(" / ")[1:]
        else:
            continue  # the step's `.lower()`: no module's attribute
        names += [f"{prefix}.{member.strip()}" for member in members
                  if member.strip() != "..."]
    return names


SURFACE = surface()


def test_the_paragraph_was_read():
    assert len(SURFACE) >= 38 and len(set(SURFACE)) == len(SURFACE)
    for decoder in DECODERS:
        assert f"models.{decoder}.flash_attention_fn" in SURFACE


@pytest.mark.parametrize("name", SURFACE)
def test_a_name_the_benchmark_calls_is_on_its_module(name):
    head, *rest = name.split(".")
    if head == "models":
        found = importlib.import_module(f"horovod_tpu.models.{rest[0]}")
        rest = rest[1:]
    else:
        found = importlib.import_module("horovod_tpu")
    for attribute in rest:
        found = getattr(found, attribute)
    assert found is not None


# Parameter trees of the toy models, path -> dtype[shape], as
# ``jax.eval_shape(model.init, ...)`` gave them on PR 42's parent (bafff63).
# A helper of models/parts.py that became a submodule would move a leaf.
F32 = "float32"
QKV_NORMS = {"attention/q_norm/scale": (64,), "attention/k_norm/scale": (64,)}


def square_attention(width, kv_width=None):
    kv_width = kv_width or width
    return {"attention/query/kernel": (width, width),
            "attention/key/kernel": (width, kv_width),
            "attention/value/kernel": (width, kv_width),
            "attention/out/kernel": (width, width)}


def experts(hidden, width, prefix="moe/"):
    return {prefix + "experts_gate": (8, hidden, width),
            prefix + "experts_up": (8, hidden, width),
            prefix + "experts_down": (8, width, hidden)}


def top(hidden, vocab):
    return {"token_embeddings/embedding": (vocab, hidden),
            "ln_out/scale": (hidden,), "lm_head": (hidden, vocab)}


def layers(*kinds):
    return {f"layer_{i}/{path}": shape for i, kind in enumerate(kinds)
            for path, shape in kind.items()}


OLMOE_LAYER = {**square_attention(64), **QKV_NORMS, **experts(64, 32),
               "moe/router": (64, 8), "ln_attn/scale": (64,),
               "ln_moe/scale": (64,)}
HYBRID_MLP = {"ln_mixer/scale": (64,), "ln_mlp/scale": (64,),
              "mlp/gate/kernel": (64, 96), "mlp/up/kernel": (64, 96),
              "mlp/down/kernel": (96, 64)}
HYBRID_LINEAR = {
    **{"linear_attention/" + path: shape for path, shape in {
        "query/kernel": (64, 32), "key/kernel": (64, 32),
        "value/kernel": (64, 64), "gate/kernel": (64, 64),
        "query_conv": (32, 4), "key_conv": (32, 4), "value_conv": (64, 4),
        "beta/kernel": (64, 4), "decay/kernel": (64, 4), "A_log": (4,),
        "dt_bias": (4,), "o_norm/scale": (16,), "out/kernel": (64, 64),
    }.items()}, **HYBRID_MLP}
HYBRID_FULL = {**square_attention(64), **QKV_NORMS, **HYBRID_MLP}
SMALLTHINKER_LAYER = {
    "attention/query/kernel": (56, 112), "attention/key/kernel": (56, 16),
    "attention/value/kernel": (56, 16), "attention/out/kernel": (112, 56),
    **experts(56, 24), "router": (56, 8), "ln_attn/scale": (56,),
    "ln_moe/scale": (56,)}
SDAR_LAYER = {**square_attention(64, 16), "attention/q_norm/scale": (8,),
              "attention/k_norm/scale": (8,), **experts(64, 24),
              "moe/router": (64, 8), "ln_attn/scale": (64,),
              "ln_moe/scale": (64,)}
GRANITE_MLP = {"ln_mixer/scale": (32,), "ln_mlp/scale": (32,),
               "mlp/input/kernel": (32, 96), "mlp/output/kernel": (48, 32)}
GRANITE_MAMBA = {
    **{"mamba/" + path: shape for path, shape in {
        "in_proj/kernel": (32, 168), "conv": (96, 4), "conv_bias": (96,),
        "A_log": (8,), "dt_bias": (8,), "D": (8,), "norm/scale": (64,),
        "out_proj/kernel": (64, 32)}.items()}, **GRANITE_MLP}
GRANITE_ATTENTION = {**square_attention(32, 16), **GRANITE_MLP}
# models/nemotron_h.py is PR 47's: its tree as that PR made it, the Mamba-2
# leaves under the names Granite's have (one body: models/mamba2.py)
NEMOTRON_MAMBA = {
    **{"mamba/" + path: shape for path, shape in {
        "in_proj/kernel": (48, 200), "conv": (128, 4), "conv_bias": (128,),
        "A_log": (8,), "dt_bias": (8,), "D": (8,), "norm/scale": (64,),
        "out_proj/kernel": (64, 48)}.items()}, "ln/scale": (48,)}
NEMOTRON_EXPERTS = {
    "moe/router": (48, 8), "moe/experts_up": (8, 48, 24),
    "moe/experts_down": (8, 24, 48), "shared/up/kernel": (48, 40),
    "shared/down/kernel": (40, 48), "ln/scale": (48,)}
NEMOTRON_ATTENTION = {
    "attention/query/kernel": (48, 64), "attention/key/kernel": (48, 16),
    "attention/value/kernel": (48, 16), "attention/out/kernel": (64, 48),
    "ln/scale": (48,)}
# models/joyai_flash.py is PR 52's: its tree as that PR made it, latent
# attention's leaves from models/latent.py (Kimi Linear's names where the
# two share a projection), the prediction module's beside the stack's
LATENT = {"attention/" + path: shape for path, shape in {
    "q_a/kernel": (64, 48), "q_norm/scale": (48,), "q_b/kernel": (48, 96),
    "kv_a/kernel": (64, 40), "kv_norm/scale": (32,),
    "kv_b/kernel": (32, 128), "out/kernel": (64, 64)}.items()}
JOYAI_NORMS = {"ln_attn/scale": (64,), "ln_ffn/scale": (64,)}
JOYAI_DENSE = {**LATENT, **JOYAI_NORMS, "mlp/gate/kernel": (64, 96),
               "mlp/up/kernel": (64, 96), "mlp/down/kernel": (96, 64)}
JOYAI_EXPERTS = {**LATENT, **JOYAI_NORMS, **experts(64, 24),
                 "moe/router": (64, 8), "shared/gate/kernel": (64, 24),
                 "shared/up/kernel": (64, 24), "shared/down/kernel": (24, 64)}
# models/lfm2.py is PR 54's: its tree as that PR made it, a conv mixer's
# three leaves and the attention's with its two scales a head's lanes wide
LFM2_NORMS = {"ln_mixer/scale": (64,), "ln_ffn/scale": (64,)}
LFM2_CONV = {"conv/in_proj/kernel": (64, 192), "conv/conv": (64, 3),
             "conv/out_proj/kernel": (64, 64), **LFM2_NORMS}
LFM2_ROUTED = {**experts(64, 24), "moe/router": (64, 8)}
LFM2_ATTENTION = {**square_attention(64, 16), **LFM2_NORMS, **LFM2_ROUTED,
                  "attention/q_norm/scale": (8,),
                  "attention/k_norm/scale": (8,)}

TREES = {
    "olmoe": ("Olmoe", "OLMOE_TINY", 1, {
        **top(64, 512), **layers(OLMOE_LAYER, OLMOE_LAYER)}),
    "olmo_hybrid": ("OlmoHybrid", "OLMO_HYBRID_TINY", 1, {
        **top(64, 512), **layers(HYBRID_LINEAR, HYBRID_LINEAR, HYBRID_LINEAR,
                                 HYBRID_FULL)}),
    "smallthinker": ("SmallThinker", "SMALLTHINKER_TINY", 1, {
        **top(56, 256), **layers(*[SMALLTHINKER_LAYER] * 4)}),
    "sdar": ("Sdar", "SDAR_TINY", 2, {
        **top(64, 256), **layers(SDAR_LAYER, SDAR_LAYER)}),
    "granite": ("Granite", "GRANITE_TINY", 1, {
        "embedding": (256, 32), "ln_out/scale": (32,),
        **layers(GRANITE_MAMBA, GRANITE_MAMBA, GRANITE_ATTENTION,
                 GRANITE_MAMBA)}),
    "nemotron_h": ("NemotronH", "NEMOTRON_H_TINY", 1, {
        **top(48, 256),
        **layers(NEMOTRON_MAMBA, NEMOTRON_EXPERTS, NEMOTRON_MAMBA,
                 NEMOTRON_ATTENTION, NEMOTRON_EXPERTS)}),
    "joyai_flash": ("JoyAIFlash", "JOYAI_FLASH_TINY", 2, {
        **top(64, 256),
        **layers(JOYAI_DENSE, JOYAI_EXPERTS, JOYAI_EXPERTS),
        **{"mtp_layer/" + path: shape
           for path, shape in JOYAI_EXPERTS.items()},
        "mtp_embed_norm/scale": (64,), "mtp_hidden_norm/scale": (64,),
        "mtp_proj/kernel": (128, 64), "mtp_norm/scale": (64,)}),
    "lfm2": ("Lfm2", "LFM2_TINY", 1, {
        "embedding": (256, 64), "ln_out/scale": (64,),
        **layers({**LFM2_CONV, "mlp/gate/kernel": (64, 96),
                  "mlp/up/kernel": (64, 96), "mlp/down/kernel": (96, 64)},
                 LFM2_ATTENTION, *[{**LFM2_CONV, **LFM2_ROUTED}] * 3)}),
}


@pytest.mark.parametrize("decoder", sorted(TREES))
def test_the_toy_models_tree_is_the_parents(decoder):
    model, config, streams, want = TREES[decoder]
    module = importlib.import_module(f"horovod_tpu.models.{decoder}")
    ids = jnp.zeros((1, 32), jnp.int32)
    tree = jax.eval_shape(
        getattr(module, model)(getattr(module, config)).init,
        jax.random.PRNGKey(0), *[ids] * streams)["params"]
    found = {"/".join(key.key for key in path): f"{leaf.dtype}{leaf.shape}"
             for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert found == {path: f"{F32}{shape}" for path, shape in want.items()}
