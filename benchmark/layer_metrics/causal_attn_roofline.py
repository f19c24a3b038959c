"""The causal flash attention kernels' share of their roofline.

FLOPs and bytes are what the algorithm needs for the call, from its
shapes, per (batch x head) slice of ``seq`` positions and ``dim`` lanes in
a type of ``itemsize`` bytes. Under the causal mask a query sees (seq + 1)
/ 2 keys on average, counted as half: the forward pass makes two products
over half of seq x seq x dim (scores, context), the backward pass five
(scores again, dV, dP, dQ, dK), however many passes an implementation
splits it into and whatever masked tiles it computes. The traffic is not
halved: q, k, v are read and the output and one float32 log-sum-exp a row
written once forward; backward reads q, k, v, the output's gradient and
three float32 rows (log-sum-exp, delta, its gradient) and writes dq, dk,
dv. Costs and bound are ``flash_attn_roofline.py``'s, the products halved.
"""

import cells
import trace_reduce

full = cells.load_code(cells.HERE, "layer_metrics", "flash_attn_roofline.py")
least_seconds = full.least_seconds


def forward_cost(slices, seq, dim, itemsize):
    flops, nbytes = full.forward_cost(slices, seq, dim, itemsize)
    return flops / 2, nbytes


def backward_cost(slices, seq, dim, itemsize):
    flops, nbytes = full.backward_cost(slices, seq, dim, itemsize)
    return flops / 2, nbytes


def read(run, params):
    seconds = trace_reduce.kernel_seconds(run.trace, params["kernel_names"])
    if seconds is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"] * config["num_attention_heads"],
             job["seq_len"],
             config["hidden_size"] // config["num_attention_heads"], 2)
    forward, forward_bound = least_seconds(forward_cost(*shape), run.peak)
    backward, backward_bound = least_seconds(backward_cost(*shape), run.peak)
    least = config["num_hidden_layers"] * (forward + backward) * run.steps
    print(f"causal_attn_roofline: least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer; took "
          f"{seconds / run.steps * 1e3:.3f} ms a step", flush=True)
    return 100.0 * least / seconds
