"""What XLA makes of the way from latent attention's projections to its
kernels, form by form, with no chip: a ranking before any chip call (PR 55).

    JAX_PLATFORMS=cpu python tools/mla_rope_forms.py [form ...]

One latent layer's operands at ``joyai-llm-flash_s8192_e16_dp1``'s shapes
(``q [1, 8192, 6144]`` as ``q_b`` wrote it, the one ``k_r [1, 8192, 64]``,
``kv_b``'s ``[1, 8192, 8192]``) through a form of the rotary split into
``parts.head_major_flash_attention``, so that the operands' layouts are the
step's, compiled for the described ``v5e:1x1`` as ``benchmark/aot.py``
compiles a step. Printed a form, forward and forward + backward: XLA's own
``cost_analysis()["bytes accessed"]`` (which prices a Pallas call at
nothing: the flash kernels' are left out of every form alike, the
``mla_rope_heads`` calls' operands and results are added) and the
instructions beside the kernels that hold as
many elements as ``q``, by opcode, type and layout.

**Bytes accessed rank forms; they are not a time.** The chip said of PR
55's forms what the ranking said (``PERF.md`` section 6), at its own rate.

Forms: ``none`` (nothing turned: Kimi Linear's path), ``plain``
(``latent.turn``, ``x cos + (x P) sin`` with ``P`` a product: every shape
the kernels do not fit), ``one_pass`` (``ops/rotary_split.py``). Two plain
rewrites that ranked worse than ``plain`` are in ``PERF.md`` and not here.
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.models import latent, parts  # noqa: E402
from horovod_tpu.ops import rotary_split  # noqa: E402

BATCH, SEQ, HEADS, NOPE, ROPE, V_DIM, THETA = 1, 8192, 32, 128, 64, 128, 3.2e7
DTYPE = jnp.bfloat16


def operands(q, shared, up, form):
    """``(q, k, v, head_major)`` of a form from the projections' outputs."""
    rows = q.shape[:2]
    cos, sin, swap = latent.rotary_split_tables(NOPE, ROPE, THETA, SEQ)
    if form == "one_pass":
        tile = rotary_split.tokens_a_step(q, HEADS, NOPE, ROPE, V_DIM)
        return rotary_split.head_major_operands(
            q, up, shared, cos[:, NOPE:], sin[:, NOPE:], HEADS, NOPE,
            tile) + (True,)
    q = q.reshape(rows + (HEADS, NOPE + ROPE))
    up = up.reshape(rows + (HEADS, NOPE + V_DIM))
    if form == "plain":
        q = latent.turn(q, cos, sin, swap, DTYPE)
        shared = latent.turn(shared, cos[:, NOPE:], sin[:, NOPE:],
                             swap[NOPE:, NOPE:], DTYPE)
    k = jnp.concatenate([up[..., :NOPE], jnp.broadcast_to(
        shared[:, :, None], rows + (HEADS, ROPE))], -1)
    return q, k, up[..., NOPE:], False


def attended(form, q, shared, up):
    q, k, v, head_major = operands(q, shared, up, form)
    return parts.head_major_flash_attention(q, k, v, DTYPE,
                                            head_major=head_major)


def beside_the_kernels(text: str, elements: int) -> dict:
    """``{(opcode, type and layout): count}`` of the entry computation's
    instructions that are no Pallas call and hold ``elements`` or more."""
    found = {}
    entry = text[text.index("ENTRY "):]
    for line in entry.splitlines():
        match = re.match(
            r"\s*(?:ROOT )?%?\S+ = (\w+)\[([\d,]*)\](\{[^ ]*\})? "
            r"([\w-]+)\(", line)
        if not match or "tpu_custom_call" in line:
            continue
        kind, dims, layout, opcode = match.groups()
        size = 1
        for dim in dims.split(","):
            size *= int(dim or 1)
        if size >= elements and opcode not in ("parameter", "tuple"):
            key = (opcode, f"{kind}[{dims}]{layout or ''}")
            found[key] = found.get(key, 0) + 1
    return found


SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
BYTES = {"bf16": 2, "f32": 4}


def kernels_bytes(text: str) -> int:
    """Operands and results of the ``mla_rope_heads`` calls, which
    ``cost_analysis()`` prices at nothing (as it does the flash kernels,
    the same in every form): a head of 192 lanes counted as it lies in
    HBM, padded to 256."""
    total = 0
    for line in text.splitlines():
        if "tpu_custom_call" not in line or rotary_split.KERNEL_NAME + "/" \
                not in line:
            continue
        result = line.split(" custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                             line).group(1)
        for kind, dims in SHAPE.findall(result) + SHAPE.findall(operands):
            dims = [int(dim) for dim in dims.split(",")]
            dims[-1] = -(-dims[-1] // 128) * 128
            size = BYTES[kind]
            for dim in dims:
                size *= dim
            total += size
    return total


def main(forms):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topology = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1",
        chips_per_host_bounds=(1, 1, 1))
    placed = SingleDeviceSharding(topology.devices[0])
    shaped = [jax.ShapeDtypeStruct(shape, DTYPE, sharding=placed)
              for shape in ((BATCH, SEQ, HEADS * (NOPE + ROPE)),
                            (BATCH, SEQ, ROPE),
                            (BATCH, SEQ, HEADS * (NOPE + V_DIM)))]
    out = jax.ShapeDtypeStruct((BATCH, SEQ, HEADS, V_DIM), DTYPE,
                               sharding=placed)
    for form in forms:
        def forward(q, shared, up, form=form):
            return attended(form, q, shared, up)

        def both(q, shared, up, bar, form=form):
            result, pull = jax.vjp(
                lambda *xs: attended(form, *xs), q, shared, up)
            return result, pull(bar)

        for name, fn, args in (("forward", forward, shaped),
                               ("forward + backward", both, shaped + [out])):
            compiled = jax.jit(fn).lower(*args).compile()
            text = compiled.as_text()
            moved = compiled.cost_analysis()["bytes accessed"]
            ours = kernels_bytes(text)
            print(f"{form:9s} {name:18s} {(moved + ours) / 1e6:9,.0f} MB "
                  f"accessed ({ours / 1e6:,.0f} of them by "
                  f"{rotary_split.KERNEL_NAME})")
            beside = beside_the_kernels(
                text, BATCH * SEQ * HEADS * (NOPE + ROPE))
            for (opcode, what), count in sorted(beside.items()):
                print(f"{'':30s}{count} x {opcode} {what}")


if __name__ == "__main__":
    main(sys.argv[1:] or ["none", "plain", "one_pass"])
