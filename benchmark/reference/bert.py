"""BERT's masked-LM loss in plain ``jax.numpy`` and float32 (Devlin et al.
2018, arXiv:1810.04805; google-research/bert ``modeling.py``): no kernels,
no flax, nothing of ``horovod_tpu`` but the names of its parameter tree.
The harness differentiates it and runs it under
``default_matmul_precision("highest")``.

Departures from the paper, all of them the product model's and followed
here so that the two compute the same function: LayerNorm's epsilon is
flax's 1e-6 (BERT: 1e-12); dropout is off; sequences are unpadded and of
one segment; the MLM head reads only the masked positions."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_NORM_EPS = 1e-6


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LAYER_NORM_EPS) * p["scale"] + p["bias"]


def gelu(x):
    """The tanh form, as in google-research/bert and ``flax.linen.gelu``."""
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p):
    """Full softmax attention; the tree keeps each projection as
    ``[hidden, heads, head_dim]`` (``out`` as ``[heads, head_dim,
    hidden]``)."""
    q = jnp.einsum("bse,ehd->bhsd", x, p["query"]["kernel"]) \
        + p["query"]["bias"][None, :, None, :]
    k = jnp.einsum("bse,ehd->bhsd", x, p["key"]["kernel"]) \
        + p["key"]["bias"][None, :, None, :]
    v = jnp.einsum("bse,ehd->bhsd", x, p["value"]["kernel"]) \
        + p["value"]["bias"][None, :, None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    context = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("bhsd,hde->bse", context, p["out"]["kernel"]) \
        + p["out"]["bias"]


def loss(config: dict, params, batch):
    ids, positions, labels, label_mask = batch
    x = (params["token_embeddings"]["embedding"][ids]
         + params["position_embeddings"]["embedding"][:ids.shape[1]][None]
         + params["type_embeddings"]["embedding"][0])
    x = layer_norm(x, params["ln_emb"])

    def layer(x, p):
        # Post-LN: sublayer, residual, LayerNorm.
        x = layer_norm(x + attention(x, p["attention"]), p["ln_attn"])
        h = gelu(x @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"])
        h = h @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]
        return layer_norm(x + h, p["ln_mlp"]), None

    # The layers are alike, so they are stacked and scanned: the compiler
    # sees one layer, not config["num_hidden_layers"] copies of it.
    layers = [params[f"layer_{i}"] for i in range(config["num_hidden_layers"])]
    x, _ = jax.lax.scan(
        layer, x, jax.tree.map(lambda *leaves: jnp.stack(leaves), *layers))
    h = jnp.take_along_axis(x, positions[..., None], axis=1)
    h = gelu(h @ params["mlm_transform"]["kernel"]
             + params["mlm_transform"]["bias"])
    h = layer_norm(h, params["mlm_ln"])
    logits = h @ params["token_embeddings"]["embedding"].T \
        + params["mlm_bias"]
    log_probs = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(log_probs, labels[..., None], -1)[..., 0]
    mask = label_mask.astype(jnp.float32)
    return -(picked * mask).sum() / mask.sum()
