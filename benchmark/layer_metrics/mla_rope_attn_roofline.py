"""The kernels' share of their roofline where every layer is latent
attention: ``mla_attn_roofline.py``'s costs, loaded from there (the causal
pairs at 192 lanes for the scores, dQ and dK and 128 for the context, dV and
dP; the rotary turn happens before the call and changes no width), over
``mla_attn_kernel_ms.py``'s events, with the count of layers from the
configuration's own ``kinds``: the stack's and the prediction module's."""

import cells

kimi = cells.load_code(cells.HERE, "layer_metrics", "mla_attn_roofline.py")


def read(run, params):
    seconds = kimi.kernels.kernel_seconds(run, params)
    if seconds is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"] * config["num_attention_heads"],
             job["seq_len"],
             config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
             config["v_head_dim"], 2)
    forward, forward_bound = kimi.least_seconds(
        kimi.forward_cost(*shape), run.peak)
    backward, backward_bound = kimi.least_seconds(
        kimi.backward_cost(*shape), run.peak)
    layers = sum(mixer == "mla" for mixer, _ in run.cell.code.kinds(config))
    least = layers * (forward + backward) * run.steps
    print(f"mla_rope_attn_roofline: {layers} layers, the prediction "
          f"module's among them; least {forward * 1e3:.4f} ms forward "
          f"({forward_bound}-bound) + {backward * 1e3:.4f} ms backward "
          f"({backward_bound}-bound) a layer; took "
          f"{seconds / run.steps * 1e3:.3f} ms a step", flush=True)
    return 100.0 * least / seconds
