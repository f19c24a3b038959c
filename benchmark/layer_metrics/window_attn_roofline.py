"""The windowed flash attention kernels' share of their roofline, and the
cost functions of attention with a band mask and grouped keys and values.

FLOPs are what the mathematics needs for exactly the (query, key) pairs the
mask leaves, ``0 <= i - j < window``: ``pairs`` of them a query head, 2
products of ``dim`` multiply-adds a pair forward (scores, context) and 5
backward (scores again, dV, dP, dQ, dK), however many tiles an
implementation computes and whatever it masks inside them. Bytes are what
the call must move once: q and the output (forward), q, the output's
gradient and dq (backward) and the float32 rows (log-sum-exp forward;
log-sum-exp, delta and its gradient backward) once a QUERY head; k, v
(forward) and k, v, dk, dv (backward) once a KEY/VALUE head, which
``group`` query heads share. Bound and peaks as ``flash_attn_roofline.py``.
"""

import cells

kernels = cells.load_code(cells.HERE, "layer_metrics",
                          "window_attn_kernel_ms.py")
least_seconds = cells.load_code(
    cells.HERE, "layer_metrics", "flash_attn_roofline.py").least_seconds
# (query, key) pairs of one head under the mask: the count the
# configuration's model FLOPs use
visible_pairs = cells.load_code(
    cells.HERE, "configs", "smallthinker.py").visible_pairs


def forward_cost(q_slices, kv_slices, seq, dim, itemsize, pairs):
    return (q_slices * 2 * 2.0 * pairs * dim,
            q_slices * (2.0 * seq * dim * itemsize + 4.0 * seq)
            + kv_slices * 2.0 * seq * dim * itemsize)


def backward_cost(q_slices, kv_slices, seq, dim, itemsize, pairs):
    return (q_slices * 5 * 2.0 * pairs * dim,
            q_slices * (3.0 * seq * dim * itemsize + 3 * 4.0 * seq)
            + kv_slices * 4.0 * seq * dim * itemsize)


def roofline(run, params, kind: str, name: str):
    """``kind``'s layers: least seconds for their calls over the device
    seconds their kernels took, in percent."""
    seconds = kernels.kernel_seconds(run, params)
    if seconds is None or not seconds[kind] or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    seq = job["seq_len"]
    pairs = visible_pairs(
        seq, config["sliding_window_size"] if kind == "window" else None)
    shape = (job["rows_per_chip"] * config["num_attention_heads"],
             job["rows_per_chip"] * config["num_key_value_heads"],
             seq, config["head_dim"], 2, pairs)
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    count = kernels.layers(config)[kind]
    least = count * (forward + backward) * run.steps
    print(f"{name}: {count} {kind} layer(s), {pairs} pairs a head; least "
          f"{forward * 1e3:.4f} ms forward ({forward_bound}-bound) + "
          f"{backward * 1e3:.4f} ms backward ({backward_bound}-bound) a "
          f"layer; took {seconds[kind] / run.steps * 1e3:.3f} ms a step",
          flush=True)
    return 100.0 * least / seconds[kind]


def read(run, params):
    return roofline(run, params, "window", "window_attn_roofline")
