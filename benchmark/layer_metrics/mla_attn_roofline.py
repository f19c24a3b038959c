"""The latent-attention kernels' share of their roofline, and the cost
functions of causal attention whose scores contract over one width and
whose context has another.

FLOPs are what the mathematics needs for exactly the (query, key) pairs the
causal mask leaves, ``S (S + 1) / 2`` a head, at the lanes each product
has: forward the scores over ``qk`` lanes and the context over ``v``;
backward the scores again, dQ and dK over ``qk``, dV and dP over ``v``;
however many tiles an implementation computes, whatever it masks inside
them and whatever it pads a block of 192 lanes to. Bytes are what the call
must move once a head: q, k at ``qk`` lanes, v and the output at ``v``
(forward); q, k, dq, dk at ``qk``, v, the output's gradient and dv at ``v``
(backward); the float32 rows (log-sum-exp forward; log-sum-exp, delta and
its gradient backward). Bound and peaks as ``flash_attn_roofline.py``.
"""

import cells

kernels = cells.load_code(cells.HERE, "layer_metrics",
                          "mla_attn_kernel_ms.py")
least_seconds = cells.load_code(
    cells.HERE, "layer_metrics", "flash_attn_roofline.py").least_seconds


def forward_cost(slices, seq, qk, v, itemsize):
    pairs = seq * (seq + 1) / 2
    return (slices * 2.0 * pairs * (qk + v),
            slices * (2.0 * seq * (qk + v) * itemsize + 4.0 * seq))


def backward_cost(slices, seq, qk, v, itemsize):
    pairs = seq * (seq + 1) / 2
    return (slices * 2.0 * pairs * (3 * qk + 2 * v),
            slices * (seq * (4.0 * qk + 3.0 * v) * itemsize
                      + 3 * 4.0 * seq))


def read(run, params):
    seconds = kernels.kernel_seconds(run, params)
    if seconds is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"] * config["num_attention_heads"],
             job["seq_len"],
             config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
             config["v_head_dim"], 2)
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    layers = len(config["linear_attn_config"]["full_attn_layers"])
    least = layers * (forward + backward) * run.steps
    print(f"mla_attn_roofline: {layers} layer(s); least "
          f"{forward * 1e3:.4f} ms forward ({forward_bound}-bound) + "
          f"{backward * 1e3:.4f} ms backward ({backward_bound}-bound) a "
          f"layer; took {seconds / run.steps * 1e3:.3f} ms a step",
          flush=True)
    return 100.0 * least / seconds
