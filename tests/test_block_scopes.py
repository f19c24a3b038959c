"""The models' block scopes and the owners read back from a step's text:
every model's toy step through ``make_train_step`` carries the block scopes
it should, none of them innermost around a kernel, and they are metadata
only; ``owner_of`` over name stacks; ``instruction_owners`` and the booking
rule on hand-made HLO text."""

from __future__ import annotations

import contextlib
import dataclasses
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu import attribution, profiler
from test_phase_scopes import without_metadata

B = attribution.SCOPE_PREFIX + "block."
COMMON = {B + "embed", B + "attn_proj", B + "norm", B + "head"}


def bert_loss():
    from horovod_tpu.models import bert

    config = dataclasses.replace(bert.BERT_TINY, dropout_rate=0.0)
    model = bert.Bert(config, attention_fn=partial(
        bert.flash_attention_fn, interpret=True))

    def loss(params, batch):
        ids, positions, labels = batch
        _, logits = model.apply({"params": params}, ids, train=True,
                                masked_positions=positions)
        return bert.mlm_loss(logits, labels, jnp.ones_like(labels))

    def batch(rows):
        ids = jnp.zeros((rows, 128), jnp.int32)
        return ids, ids[:, :4], ids[:, :4]

    params = jax.jit(bert.Bert(config).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return loss, params, batch


def resnet_loss():
    from horovod_tpu.models import resnet
    from horovod_tpu.models.lenet import cross_entropy_loss

    model = resnet.ResNet(stage_sizes=[1, 1], num_classes=10, num_filters=8)

    def loss(variables, batch):
        images, labels = batch
        logits, _ = model.apply(variables, images, train=True,
                                mutable=["batch_stats"])
        return cross_entropy_loss(logits, labels, num_classes=10)

    def batch(rows):
        return (jnp.ones((rows, 32, 32, 3), jnp.float32),
                jnp.zeros((rows,), jnp.int32))

    variables = jax.jit(partial(model.init, train=True))(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    return loss, dict(variables), batch


def decoder_loss(module_name, model_name, tiny_name, length, **flash):
    import importlib

    module = importlib.import_module("horovod_tpu.models." + module_name)
    config = dataclasses.replace(getattr(module, tiny_name),
                                 dtype=jnp.float32)
    model = getattr(module, model_name)(config, attention_fn=partial(
        module.flash_attention_fn, interpret=True, **flash))
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, length), jnp.int32))["params"]
    return (partial(module.causal_lm_loss, model), params,
            lambda rows: jnp.zeros((rows, length + 1), jnp.int32))


MODELS = {
    "bert": (bert_loss, COMMON | {B + "ffn"}),
    "resnet": (resnet_loss, {B + "stem", B + "stage", B + "head"}),
    "olmoe": (partial(decoder_loss, "olmoe", "Olmoe", "OLMOE_TINY", 32,
                      block=16), COMMON),
    "olmo_hybrid": (partial(decoder_loss, "olmo_hybrid", "OlmoHybrid",
                            "OLMO_HYBRID_TINY", 32, block=16),
                    COMMON | {B + "ffn"}),
    "smallthinker": (partial(decoder_loss, "smallthinker", "SmallThinker",
                             "SMALLTHINKER_TINY", 32, block=16), COMMON),
    # a layer is a mixer alone: block.ffn is around an E layer's experts
    "nemotron_h": (partial(decoder_loss, "nemotron_h", "NemotronH",
                           "NEMOTRON_H_TINY", 32, block=16),
                   COMMON | {B + "ffn"}),
}
TEXTS: dict = {}
DEFINED = re.compile(r"^\s*(?:ROOT |ENTRY )?%([\w.-]+) (?:=|\()", re.M)


def without_names(text: str) -> str:
    """``without_metadata`` and every instruction and computation renamed
    by its place in the text: XLA names some instructions after the last
    components of their name stack (``%jvp_jit_take_along_axis__.18``
    becomes ``%jit_take_along_axis_.35`` once a scope stands between the
    two), so a scope moves names and nothing they name."""
    text = without_metadata(text)
    names: dict = {}
    for name in DEFINED.findall(text):
        names.setdefault(name, f"n{len(names)}")
    return re.sub(r"%([\w.-]+)",
                  lambda found: "%" + names.get(found.group(1),
                                                found.group(1)), text)


def compiled_text(name: str) -> str:
    """The toy step of model ``name`` through the factory, compiled."""
    import horovod_tpu as hvd

    dp = hvd.data_parallel
    loss, params, batch = MODELS[name][0]()
    optimizer = hvd.DistributedOptimizer(optax.adamw(1e-4))
    step = dp.make_train_step(loss, optimizer)
    params = dp.replicate(params)
    return step.lower(params, dp.replicate(optimizer.init(params)),
                      dp.shard_batch(batch(hvd.size()))).compile().as_text()


def scoped_text(name: str) -> str:
    if name not in TEXTS:
        TEXTS[name] = compiled_text(name)
    return TEXTS[name]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_models_step_holds_its_block_scopes(name):
    scopes = profiler.instruction_scopes(scoped_text(name)).values()
    blocks = {part.rsplit("(", 1)[-1].rstrip(")")
              for scope in scopes for part in scope.split("/")} & set(
                  attribution.BLOCK_SCOPE_NAMES)
    assert blocks == MODELS[name][1]
    # the backward pass's operations carry them too
    for block in blocks:
        assert any("transpose(" in scope and block in scope
                   for scope in scopes), block
    owners = {profiler.booked_to(owner) for owner in
              profiler.instruction_owners(scoped_text(name)).values()}
    assert blocks | {"hvd.optimizer"} <= owners


@pytest.mark.parametrize("name", sorted(MODELS))
def test_no_block_scope_is_innermost_around_a_kernel(name):
    """XLA names a custom call after the innermost component of its name
    stack: a kernel keeps a phase scope between it and any block."""
    from horovod_tpu.ops.attention import KERNEL_NAME

    kernels = [scope.split("/") for scope in
               profiler.instruction_scopes(scoped_text(name)).values()]
    kernels = [parts[:parts.index(KERNEL_NAME)] for parts in kernels
               if KERNEL_NAME in parts]
    assert bool(kernels) == (name != "resnet")
    for before in kernels:
        named = [part.rsplit("(", 1)[-1].rstrip(")") for part in before
                 if attribution.SCOPE_PREFIX in part]
        assert named[-1] in ("hvd.attn.fwd", "hvd.attn.bwd"), before
    assert any(B + "attn_proj" in before for before in kernels) == bool(
        kernels)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_block_scopes_are_metadata_only(name, monkeypatch):
    text = scoped_text(name)
    for module in list(sys.modules.values()):
        if getattr(module, "__name__", "").startswith("horovod_tpu") and \
                hasattr(module, "annotate_collective"):
            monkeypatch.setattr(module, "annotate_collective",
                                lambda scope: contextlib.nullcontext())
    jax.clear_caches()
    bare = compiled_text(name)
    jax.clear_caches()
    assert "hvd." not in bare
    assert without_names(bare) == without_names(text)


@pytest.mark.parametrize("scope, owner", [
    # a phase inside a block is the phase's
    ("jit(s)/jvp(Bert)/layer_0/hvd.block.attn_proj/attention/"
     "jit(flash_attention)/hvd.attn.fwd/flash_attention/pallas_call",
     "hvd.attn.fwd"),
    ("jit(s)/jvp(OlmoHybrid)/layer_0/hvd.block.attn_proj/linear_attention/"
     "hvd.linattn.conv/mul", "hvd.linattn.conv"),
    # a block inside a phase stays the phase's
    ("jit(s)/jvp(M)/hvd.linattn.gate/o_norm/hvd.block.norm/mul",
     "hvd.linattn.gate"),
    # no phase: the innermost block
    ("jit(s)/jvp(Bert)/layer_0/hvd.block.ffn/mlp_in/dot_general",
     "hvd.block.ffn"),
    ("jit(s)/jvp(M)/hvd.block.attn_proj/q_norm/hvd.block.norm/mul",
     "hvd.block.norm"),
    # a transformation wraps the outermost name of what it transforms
    ("jit(s)/transpose(jvp(hvd.block.head))/dot_general", "hvd.block.head"),
    ("jit(s)/jvp(M)/layer_1/vmap(hvd.block.norm)/add", "hvd.block.norm"),
    ("jit(s)/transpose(jvp(M))/hvd.block.stage/Bottleneck_0/conv",
     "hvd.block.stage"),
    # neither
    ("jit(s)/jvp(M)/layer_0/dot_general", None),
    ("jit(s)/hvd.block.heads/mul", None),
    ("jit(s)/hvd.optimizer/mul", "hvd.optimizer"),
])
def test_owner_of_takes_the_innermost_phase_then_the_innermost_block(
        scope, owner):
    assert profiler.owner_of(scope) == owner
    # phase_of reads what it read: a block is no phase
    assert profiler.phase_of(scope) == (
        owner if owner in attribution.PHASE_SCOPE_NAMES else None)


def meta(scope):
    return f'metadata={{op_name="jit(s)/{scope}" stack_frame_id=1}}'


HLO = f"""HloModule jit_s, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b), {meta("jvp(M)/hvd.block.norm/reduce_sum")}
}}

%fused_one (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0:T(8)(2,1)}} parameter(0)
  %c0 = f32[] constant(2), {meta("jvp(M)/hvd.block.ffn/mul")}
  %bc0 = f32[8]{{0}} broadcast(%c0), dimensions={{}}
  ROOT %mul.0 = f32[8]{{0}} multiply(%p0, %bc0), {meta("jvp(M)/hvd.block.ffn/mlp_in/mul")}
}}

%fused_two (p1: f32[8], p2: f32[8]) -> f32[8] {{
  %p1 = f32[8]{{0}} parameter(0)
  %p2 = f32[8]{{0}} parameter(1)
  %dot.1 = f32[8]{{0}} multiply(%p1, %p2), {meta("transpose(jvp(M))/vmap(hvd.moe.experts)/dot_general")}
  ROOT %add.1 = f32[8]{{0}} add(%dot.1, %p2), {meta("hvd.optimizer/add")}
}}

%fused_none (p3: f32[8]) -> f32[8] {{
  %p3 = f32[8]{{0}} parameter(0)
  ROOT %copy.9 = f32[8]{{0}} copy(%p3)
}}

%body (carry: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %carry = (s32[], f32[8]{{0}}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %x = f32[8]{{0}} get-tuple-element(%carry), index=1
  %fusion.5 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_one
  %neg.5 = f32[8]{{0}} negate(%fusion.5), {meta("jvp(M)/hvd.linattn.scan/while/body/neg")}
  ROOT %t = (s32[], f32[8]{{0}}) tuple(%i, %neg.5)
}}

%cond (carry.1: (s32[], f32[8])) -> pred[] {{
  %carry.1 = (s32[], f32[8]{{0}}) parameter(0)
  %i.1 = s32[] get-tuple-element(%carry.1), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}}

ENTRY %main (arg0: f32[8], arg1: f32[8]) -> f32[8] {{
  %arg0 = f32[8]{{0}} parameter(0)
  %arg1 = f32[8]{{0}} parameter(1)
  %fusion.1 = f32[8]{{0:T(8)(2,1)}} fusion(%arg0), kind=kLoop, calls=%fused_one
  %copy.2 = f32[8]{{0}} copy(%fusion.1)
  %fusion.2 = f32[8]{{0}} fusion(/*index=0*/%arg1, %copy.2), kind=kOutput, calls=%fused_two, {meta("transpose(jvp(M))/vmap(hvd.moe.experts)/dot_general")}
  %fusion.3 = f32[8]{{0}} fusion(%arg0, %fusion.2), kind=kLoop, calls=%fused_two
  %fusion.4 = f32[8]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fused_none
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{{0}}) tuple(%zero, %fusion.4)
  %while.6 = (s32[], f32[8]{{0}}) while(%init), condition=%cond, body=%body
  %out = f32[8]{{0}} get-tuple-element(%while.6), index=1
  %copy-start.7 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(%out)
  %copy-done.7 = f32[8]{{0}} copy-done(%copy-start.7)
  ROOT %reduce.8 = f32[8]{{0}} add(%copy-done.7, %copy-done.7), {meta("jvp(M)/hvd.block.norm/add")}
}}
"""


class TestInstructionOwners:
    @pytest.fixture(scope="class")
    def owners(self):
        return profiler.instruction_owners(HLO)

    def test_a_fusion_with_one_owner_inside_is_that_owners(self, owners):
        found = owners["fusion.1"]
        assert found.own is None
        assert found.inside == {"hvd.block.ffn"}
        assert (found.opcode, found.shape) == ("fusion",
                                               "f32[8]{0:T(8)(2,1)}")
        assert profiler.booked_to(found) == "hvd.block.ffn"

    def test_own_goes_before_what_is_inside(self, owners):
        found = owners["fusion.2"]
        assert found.own == "hvd.moe.experts"
        assert found.inside == {"hvd.moe.experts", "hvd.optimizer"}
        assert profiler.booked_to(found) == "hvd.moe.experts"

    def test_a_fusion_with_two_owners_and_none_of_its_own_is_shared(
            self, owners):
        found = owners["fusion.3"]
        assert found.own is None and len(found.inside) == 2
        assert profiler.booked_to(found) == profiler.OWNER_SHARED

    def test_a_fusion_with_none_is_unowned_and_names_its_neighbours(
            self, owners):
        found = owners["fusion.4"]
        assert found.own is None and not found.inside
        assert profiler.booked_to(found) == profiler.OWNER_UNOWNED
        # its operand comes from a shared fusion; its first user, past the
        # tuple that computes nothing, is the loop, which two owners share
        assert found.neighbours == (profiler.OWNER_SHARED,
                                    profiler.OWNER_SHARED)

    def test_a_while_holds_what_its_body_holds_all_the_way_down(
            self, owners):
        found = owners["while.6"]
        assert found.own is None
        assert found.inside == {"hvd.block.ffn", "hvd.linattn.scan"}
        assert profiler.booked_to(found) == profiler.OWNER_SHARED
        # the instructions inside it have records of their own
        assert profiler.booked_to(owners["neg.5"]) == "hvd.linattn.scan"
        assert profiler.booked_to(owners["fusion.5"]) == "hvd.block.ffn"
        # a body's parameter ends the chain of first operands
        assert owners["fusion.5"].neighbours == (None, "hvd.linattn.scan")

    def test_a_copy_names_the_owners_on_both_sides(self, owners):
        assert owners["copy.2"].neighbours == ("hvd.block.ffn",
                                               "hvd.moe.experts")
        assert profiler.booked_to(owners["copy.2"]) == profiler.OWNER_UNOWNED
        # through the halves of an asynchronous copy, which own nothing
        assert owners["copy-start.7"].neighbours == (profiler.OWNER_SHARED,
                                                     "hvd.block.norm")
        assert owners["copy-done.7"].neighbours == (profiler.OWNER_SHARED,
                                                    "hvd.block.norm")

    def test_what_computes_nothing_owns_nothing_inside(self, owners):
        # %c0 carries hvd.block.ffn's name stack and is a constant: the
        # fusion's owner comes from the multiply
        assert owners["c0"].own == "hvd.block.ffn"
        stripped = HLO.replace(
            meta("jvp(M)/hvd.block.ffn/mlp_in/mul"), "")
        assert not profiler.instruction_owners(stripped)["fusion.1"].inside

    def test_a_reductions_region_is_not_read_for_inside(self, owners):
        assert owners["reduce.8"].own == "hvd.block.norm"
        assert not owners["reduce.8"].inside

    def test_every_instruction_has_a_record(self, owners):
        assert {"arg0", "p0", "lt", "t", "out", "add.0"} <= set(owners)
        assert owners["out"].opcode == "get-tuple-element"

    def test_a_text_without_a_block_scope_is_refused(self):
        stale = HLO.replace("hvd.block.", "hvd.blocks.")
        with pytest.raises(ValueError, match="no block scope") as refused:
            profiler.instruction_owners(stale)
        assert "persistent compilation cache" in str(refused.value)
        assert "hvd.block.attn_proj" in str(refused.value)
        # instruction_scopes keeps its contract: phases are enough for it
        assert profiler.instruction_scopes(stale)["neg.5"].endswith("neg")


@pytest.mark.parametrize("own, inside, booking", [
    ("hvd.block.ffn", {"hvd.optimizer", "hvd.block.ffn"}, "hvd.block.ffn"),
    (None, {"hvd.optimizer"}, "hvd.optimizer"),
    (None, {"hvd.optimizer", "hvd.block.ffn"}, profiler.OWNER_SHARED),
    (None, set(), profiler.OWNER_UNOWNED),
])
def test_the_booking_rule(own, inside, booking):
    owner = profiler.Owner(own, frozenset(inside), (None, None), "fusion",
                           "f32[8]")
    assert profiler.booked_to(owner) == booking


def test_the_block_vocabulary_stands_beside_the_phases():
    assert not set(attribution.BLOCK_SCOPE_NAMES) & set(
        attribution.PHASE_SCOPE_NAMES)
    assert all(name.startswith("hvd.block.")
               for name in attribution.BLOCK_SCOPE_NAMES)
    assert len(attribution.BLOCK_SCOPE_NAMES) == 7
    assert len(attribution.PHASE_SCOPE_NAMES) == 17
    assert "hvd.shortconv.mix" in attribution.PHASE_SCOPE_NAMES
