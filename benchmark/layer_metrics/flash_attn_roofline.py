"""The flash attention kernels' share of their roofline.

FLOPs and bytes are what the algorithm needs for the call, from its
shapes, per (batch x head) slice of ``seq`` positions and ``dim`` lanes in
a type of ``itemsize`` bytes: the forward pass makes two seq x seq x dim
products (scores, context), reads q, k, v and writes the output and one
float32 log-sum-exp a row; flash attention's backward pass makes five
(scores again, dV, dP, dQ, dK), reads q, k, v, the output's gradient and
three float32 rows (log-sum-exp, delta, its gradient) and writes dq, dk,
dv.
"""

import trace_reduce


def forward_cost(slices, seq, dim, itemsize):
    return (slices * 2 * 2.0 * seq * seq * dim,
            slices * (4.0 * seq * dim * itemsize + 4.0 * seq))


def backward_cost(slices, seq, dim, itemsize):
    return (slices * 5 * 2.0 * seq * seq * dim,
            slices * (7.0 * seq * dim * itemsize + 3 * 4.0 * seq))


def least_seconds(cost, peak):
    flops, nbytes = cost
    by_compute = flops / peak["bf16_flops_per_s"]
    by_memory = nbytes / peak["hbm_bytes_per_s"]
    return max(by_compute, by_memory), (
        "compute" if by_compute >= by_memory else "memory")


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
    print(f"flash_attn_roofline: least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer; took "
          f"{seconds / run.steps * 1e3:.3f} ms a step", flush=True)
    return 100.0 * least / seconds
