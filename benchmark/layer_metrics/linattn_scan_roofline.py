"""The gated delta rule's share of its roofline.

FLOPs and bytes are what the chunk-parallel form needs for one layer's
call, from its shapes: ``slices`` (batch x heads held) sequences of ``seq``
tokens in chunks of ``chunk``, keys of ``d_k`` and values of ``d_v`` lanes
in a type of ``itemsize`` bytes. Forward, a chunk makes, in multiply-adds,

* the causal half of ``K K^T``, of ``Q K^T`` and of the product of the
  latter with ``V'``, and the forward substitution of ``(I + A)`` against
  ``[V | K]`` (a triangle against ``d_v + d_k`` columns): ``chunk^2 / 2``
  times ``d_k + d_k + d_v + (d_v + d_k)``;
* three products with the state, ``W S0``, ``Q S0`` and ``K^T V'``:
  ``3 chunk d_k d_v``;

the masked halves, the diagonal and everything elementwise count for
nothing, so an implementation can only do more. The backward pass is twice
that (every product has two gradients; nothing recomputed). The traffic is
the operands once: forward reads q, k, v and two float32 gates a token a
head and writes the output; backward reads those and the output's gradient
and writes five gradients. The states that cross chunks (``seq / chunk``
of ``d_k x d_v`` float32 a slice) count for nothing: an implementation may
recompute them. Bound: ``flash_attn_roofline.py``'s.
"""

import cells

scan = cells.load_code(cells.HERE, "layer_metrics", "linattn_scan_ms.py")
least_seconds = cells.load_code(
    cells.HERE, "layer_metrics", "flash_attn_roofline.py").least_seconds


def forward_cost(slices, seq, chunk, d_k, d_v, itemsize):
    macs = seq / chunk * (chunk * chunk / 2 * (3 * d_k + 2 * d_v)
                          + 3 * chunk * d_k * d_v)
    nbytes = seq * ((2 * d_k + 2 * d_v) * itemsize + 2 * 4.0)
    return slices * 2.0 * macs, slices * nbytes


def backward_cost(slices, seq, chunk, d_k, d_v, itemsize):
    flops, _ = forward_cost(slices, seq, chunk, d_k, d_v, itemsize)
    nbytes = seq * ((4 * d_k + 3 * d_v) * itemsize + 4 * 4.0)
    return 2 * flops, slices * nbytes


def read(run, params):
    ms = scan.scope_ms(run, params["scopes"])
    if ms is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"] * config["heads_here"], job["seq_len"],
             config["training"]["scan_chunk"], config["linear_key_head_dim"],
             config["linear_value_head_dim"], 2)
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    layers = config["layer_types"].count("linear_attention")
    print(f"linattn_scan_roofline: least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer, {layers} layers; took "
          f"{ms:.3f} ms a step", flush=True)
    return 100.0 * layers * (forward + backward) * 1e3 / ms
