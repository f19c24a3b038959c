"""The block-diffusion attention kernels' share of their roofline, and the
cost functions of the two calls a layer makes.

The kernels under ``hvd.attn.blockdiff`` are two calls over the clean keys
and values: the clean queries under the block-causal mask (``blk(j) <=
blk(i)``: ``S (S + B) / 2`` pairs a query head) and the noisy queries over
the clean past (``blk(j) < blk(i)``: ``S (S - B) / 2``): ``S^2`` in all. The
noisy stream's own blocks (``S B`` pairs, the rest of the mask's ``S (S +
B)``) are plain XLA, ``blockdiff_attn_glue_ms``'s, and are not counted
here, so this share can only read low. FLOPs are what the mathematics
needs for exactly those pairs: 2 products of ``dim`` multiply-adds a pair
forward (scores, context) and 5 backward (scores again, dV, dP, dQ, dK),
however many tiles an implementation computes and whatever it masks inside
them. Bytes are what a call must move once, as ``window_attn_roofline.py``
counts them (its two cost functions, a call at a time): q, the output, dO,
dq and the float32 rows once a QUERY head, k, v, dk, dv once a KEY/VALUE
head. Bound and peaks as ``flash_attn_roofline.py``."""

import cells

kernels = cells.load_code(cells.HERE, "layer_metrics",
                          "blockdiff_attn_kernel_ms.py")
costs = cells.load_code(cells.HERE, "layer_metrics",
                        "window_attn_roofline.py")


# (query, key) pairs of one head under the mask, by term: the count the
# configuration's model FLOPs use
visible_pairs = cells.load_code(
    cells.HERE, "configs", "sdar.py").visible_pairs


def kernel_pairs(seq: int, block_length: int) -> dict:
    """(query, key) pairs a query head of each of the two kernel calls:
    the mask's terms but the noisy stream's own blocks."""
    pairs = visible_pairs(seq, block_length)
    return {"clean": pairs["clean"], "past": pairs["past"]}


def layer_cost(q_slices, kv_slices, seq, dim, itemsize, block_length):
    """``{"forward": (flops, bytes), "backward": (flops, bytes)}`` of one
    layer's two calls."""
    summed = {}
    for name, cost in (("forward", costs.forward_cost),
                       ("backward", costs.backward_cost)):
        calls = [cost(q_slices, kv_slices, seq, dim, itemsize, pairs)
                 for pairs in kernel_pairs(seq, block_length).values()]
        summed[name] = tuple(map(sum, zip(*calls)))
    return summed


def read(run, params):
    seconds = kernels.kernel_seconds(run, params)
    if seconds is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    cost = layer_cost(
        job["rows_per_chip"] * config["num_attention_heads"],
        job["rows_per_chip"] * config["num_key_value_heads"],
        job["seq_len"], config["head_dim"], 2, config["block_length"])
    forward, forward_bound = costs.least_seconds(cost["forward"], run.peak)
    backward, backward_bound = costs.least_seconds(cost["backward"],
                                                   run.peak)
    least = config["num_hidden_layers"] * (forward + backward) * run.steps
    print(f"blockdiff_attn_roofline: {config['num_hidden_layers']} layers "
          f"of two calls, {job['seq_len'] ** 2} pairs a head; least "
          f"{forward * 1e3:.4f} ms forward ({forward_bound}-bound) + "
          f"{backward * 1e3:.4f} ms backward ({backward_bound}-bound) a "
          f"layer; took {seconds / run.steps * 1e3:.3f} ms a step",
          flush=True)
    return 100.0 * least / seconds
