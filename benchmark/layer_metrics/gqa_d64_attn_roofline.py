"""The ``full_attention`` layers' flash attention kernels' share of their
roofline: ``window_attn_roofline.py``'s costs, loaded from there, over the
whole causal triangle, ``S (S + 1) / 2`` pairs a query head, with this
family's keys for the shapes (32 query slices a row, 8 key/value slices,
``hidden_size / num_attention_heads`` lanes: the source's config has no
``head_dim``) and the ``full_attention`` entries of ``layer_types`` for the
count of layers."""

import cells
import trace_reduce

window = cells.load_code(cells.HERE, "layer_metrics",
                         "window_attn_roofline.py")


def read(run, params):
    seconds = trace_reduce.kernel_seconds(run.trace, params["kernel_names"])
    if seconds is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    seq, heads = job["seq_len"], config["num_attention_heads"]
    shape = (job["rows_per_chip"] * heads,
             job["rows_per_chip"] * config["num_key_value_heads"],
             seq, config["hidden_size"] // heads, 2,
             window.visible_pairs(seq, None))
    forward, forward_bound = window.least_seconds(
        window.forward_cost(*shape), run.peak)
    backward, backward_bound = window.least_seconds(
        window.backward_cost(*shape), run.peak)
    layers = config["layer_types"].count("full_attention")
    least = layers * (forward + backward) * run.steps
    print(f"gqa_d64_attn_roofline: {layers} layer(s), {heads} query heads on "
          f"{config['num_key_value_heads']} of {shape[3]} lanes, "
          f"{job['rows_per_chip']} rows; least {forward * 1e3:.4f} ms "
          f"forward ({forward_bound}-bound) + {backward * 1e3:.4f} ms "
          f"backward ({backward_bound}-bound) a layer; took "
          f"{seconds / run.steps * 1e3:.3f} ms a step", flush=True)
    return 100.0 * least / seconds
