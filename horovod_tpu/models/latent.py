"""Latent attention (MLA, DeepSeek-V2/V3's), owned by no decoder: the
keys and values of every head come through one low-rank latent a token, and
the keys are completed by a few lanes that all heads read alike.

``h`` the normalised input, ``H`` heads, ``n`` = ``qk_nope_head_dim``, ``r``
= ``qk_rope_head_dim``, ``v`` = ``v_head_dim``::

    q = h W_q                                   [S, H, n + r]   (direct), or
    q = RMSNorm(h W_qa) W_qb                    (``q_lora_rank``: low-rank)
    [c | k_r] = h W_kva                         (kv_lora_rank | r)
    [k_n | v] = RMSNorm(c) W_kvb                [S, H, n | v]
    k = [k_n | k_r], the one k_r in every head
    o = softmax(causal(q k^T (n + r)^-1/2)) v;  out = concat(o) W_o

**The rotary split** (``rope_theta``): the last ``r`` lanes of every head's
query and the one ``k_r`` are turned by RoPE at the token's position, the
``n`` lanes before them are not. The pairs are the source's interleaved ones
(``rope_interleave``): lanes ``(2i, 2i + 1)`` by the angle ``t * theta ** (-2i
/ r)``. The source de-interleaves the lanes first and then turns half
against half; that is the same rotation followed by one fixed permutation of
the ``r`` lanes of ``q`` and ``k`` alike, which no score sees, so here the
lanes stay where the projections wrote them. A turned lane is ``x * cos +
partner(x) * sin`` in float32 from the stored type, rounded once. Without
``rope_theta`` nothing is turned (Kimi Linear's ``mla_use_nope``: its
recurrent layers order the tokens).

**Two ways to the kernels**, by what the layer can observe (no knob; a
trace that took the first holds the primitives ``hvd_mla_rope_queries`` and
``hvd_mla_rope_keys``):

* ``one_pass``: where the heads pair up into whole lane tiles (``n`` and
  ``v`` whole tiles, ``2 r`` one tile: 128 + 64 and 128, an even number of
  heads, tokens in whole sublane tiles: ``ops.rotary_split.tokens_a_step``)
  and ``attention_fn`` takes head-major operands
  (``parts.takes_head_major``), ``ops/rotary_split.py``'s kernels read
  ``q``, ``kv_b``'s output and ``k_r`` as the projections wrote them and
  write ``q``, ``[k_n | turn(k_r)]`` and ``v`` as ``[B, H, S, d]``, turning
  the rotary lanes on the way, and their backward kernels take ``dq``,
  ``dk``, ``dv`` back the same way (``d k_r`` summed over the heads in
  float32). What the chip showed (PR 55; the parent's numbers are PR 52's
  cell): a head of 192 lanes is one and a half lane tiles, so ``[S, H *
  192] -> [S, H, 192]`` is no view there; XLA copied ``q`` twice each way
  to get it head-major even with nothing turned, and with the turn beside
  it (a whole head ``x cos + (x P) sin``, ``P`` the pairs' swap as a
  product at ``Precision.HIGHEST``, which splits the fusion) it wrote ``q``
  in float32 and re-tiled that too: 2.8 GB a forward pass of one layer
  where the kernels move 0.75.
* ``plain``: any other shape (the toys of ``tests/``, an odd head count,
  dense attention): :func:`turn`, a head turned whole with ones and zeros in
  the tables of the ``n`` lanes that stay and the swap **as a product on the
  MXU** (exact: one 1 a column), then the reshapes and the adapter's
  transposes. The one pass keeps its arithmetic and its transpose's
  roundings bit for bit (``tests/test_joyai_flash_model.py``).

In training nothing is absorbed or cached: it is ``H``-head causal attention
whose scores contract over ``n + r`` lanes and whose context is ``v`` wide,
through ``attention_fn=`` (``parts.head_major_flash_attention``: the
multi-tile kernels at two widths, under ``hvd.attn.mla``).

``models/kimi_linear.py`` and ``models/joyai_flash.py`` call it; it imports
``parts`` and no decoder (``tests/test_decoder_imports.py``).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..attribution import SCOPE_MLA_ROPE
from ..ops.rotary_split import head_major_operands, tokens_a_step
from ..profiler import annotate_collective
from .parts import (RMSNorm, dense_causal_attention, projection,
                    takes_head_major)


def interleaved_pairs(lanes: int):
    """``(partner, sign, frequency)`` of each of the ``lanes`` rotary lanes
    in the source's interleaved layout: lane ``2i`` pairs with ``2i + 1``,
    both at frequency ``i``; the turned lane is ``x * cos + sign *
    x[partner] * sin``."""
    lane = np.arange(lanes)
    return lane ^ 1, np.where(lane % 2 == 0, -1.0, 1.0), lane // 2


def rotary_split_tables(kept: int, lanes: int, theta: float, seq: int):
    """``(cos, sin [S, kept + lanes], swap [kept + lanes, kept + lanes])``
    in float32 for :func:`turn`: ones, zeros and no partner on the ``kept``
    lanes; on the ``lanes`` after them the pairs' cosine, signed sine and
    swap at positions ``0..S-1``."""
    partner, sign, frequency = interleaved_pairs(lanes)
    inv_freq = 1.0 / theta ** (jnp.asarray(2 * frequency, jnp.float32)
                               / lanes)  # the source's arithmetic
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.ones((seq, kept), jnp.float32),
                           jnp.cos(angle)], -1)
    sin = jnp.concatenate([jnp.zeros((seq, kept), jnp.float32),
                           jnp.sin(angle) * sign.astype(np.float32)], -1)
    swap = np.zeros((kept + lanes, kept + lanes), np.float32)
    swap[kept + partner, kept + np.arange(lanes)] = 1.0
    return cos, sin, swap


def turn(x, cos, sin, swap, dtype):
    """``x [B, S, ..., D]`` turned at its positions, as ``dtype``: ``x * cos
    + (x swap) * sin`` in float32, the tables ``[S, D]`` broadcast over what
    lies between the positions and the lanes."""
    between = (1,) * (x.ndim - 3)
    cos, sin = (t.reshape(t.shape[:1] + between + t.shape[1:])
                for t in (cos, sin))
    swapped = jnp.dot(x, jnp.asarray(swap, x.dtype),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=x.dtype)
    return (x.astype(jnp.float32) * cos
            + swapped.astype(jnp.float32) * sin).astype(dtype)


class LatentAttention(nn.Module):
    """``attention_fn(q [B, S, H, n + r], k [B, S, H, n + r], v [B, S, H,
    v], dtype)`` returns the context ``[B, S, H, v]``. ``config`` is the
    model's (``hidden_size``, ``num_attention_heads``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rms_norm_eps``, ``dtype``). ``q_lora_rank`` None: the queries straight
    from the input (leaf ``query``); a rank: through ``q_a``, ``q_norm`` and
    ``q_b``. ``rope_theta`` None: no lane is turned."""
    config: Any
    attention_fn: Callable | None = None
    q_lora_rank: int | None = None
    rope_theta: float | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, nope, rope, v_dim = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim)
        rows = x.shape[:2]
        attend = self.attention_fn or dense_causal_attention
        if self.q_lora_rank is None:
            q = projection(cfg, heads * (nope + rope), "query")(x)
        else:
            q = projection(cfg, heads * (nope + rope), "q_b")(
                RMSNorm(cfg.rms_norm_eps, name="q_norm")(
                    projection(cfg, self.q_lora_rank, "q_a")(x)).astype(
                        cfg.dtype))
        tile = None  # of the one pass, where a layer takes it
        if self.rope_theta is not None and takes_head_major(attend):
            tile = tokens_a_step(q, heads, nope, rope, v_dim)

        def apart(flat):
            """The heads of what a projection wrote, where XLA splits
            them: the one pass reads the lanes as they lie."""
            return flat if tile else flat.reshape(rows + (heads, -1))

        q = apart(q)
        latent = projection(cfg, cfg.kv_lora_rank + rope, "kv_a")(x)
        shared = latent[..., cfg.kv_lora_rank:]  # k_r, every head's alike
        up = apart(projection(cfg, heads * (nope + v_dim), "kv_b")(
            RMSNorm(cfg.rms_norm_eps, name="kv_norm")(
                latent[..., :cfg.kv_lora_rank]).astype(cfg.dtype)))

        def keys(shared):
            return jnp.concatenate([
                up[..., :nope], jnp.broadcast_to(
                    shared[:, :, None], rows + (heads, rope))], -1)

        if self.rope_theta is None:
            k = keys(shared)
        else:
            with annotate_collective(SCOPE_MLA_ROPE):
                cos, sin, swap = rotary_split_tables(
                    nope, rope, self.rope_theta, rows[1])
                rotary = cos[:, nope:], sin[:, nope:]  # the lanes' that turn
                if not tile:
                    q = turn(q, cos, sin, swap, cfg.dtype)
                    k = keys(turn(shared, *rotary, swap[nope:, nope:],
                                  cfg.dtype))
        if tile:  # under the same scope, which its passes open themselves
            q, k, v = head_major_operands(q, up, shared, *rotary, heads,
                                          nope, tile)
            out = attend(q, k, v, cfg.dtype, head_major=True)
        else:
            out = attend(q, k, up[..., nope:], cfg.dtype)
        return projection(cfg, cfg.hidden_size, "out")(
            out.reshape(rows + (heads * v_dim,)))
