"""The Mamba-2 scan's share of its roofline where ``B`` and ``C`` come in
groups (Nemotron-H: 8 groups of 8 heads).

The work is the recurrence's, whatever implements it and however many
groups share a ``B``: ``ssd_scan_roofline.py``'s ``forward_cost`` and
``backward_cost``, loaded from there, with this family's keys for the
shapes (``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
``n_groups``) and the ``M`` characters of ``hybrid_override_pattern`` for
the count of layers. More groups are more bytes (a ``B`` and a ``C`` a
group) and the same operations."""

import cells

granite = cells.load_code(cells.HERE, "layer_metrics", "ssd_scan_roofline.py")


def read(run, params):
    ms = granite.scope_ms(run, params["scopes"])
    if ms is None or run.peak is None:
        return None
    config, job = run.cell.config, run.cell.job
    shape = (job["rows_per_chip"], job["seq_len"], config["mamba_num_heads"],
             config["mamba_head_dim"], config["ssm_state_size"],
             config["n_groups"],
             granite.ITEMSIZE[config["training"]["compute_dtype"]])
    forward, forward_bound = granite.least_seconds(
        granite.forward_cost(*shape), run.peak)
    backward, backward_bound = granite.least_seconds(
        granite.backward_cost(*shape), run.peak)
    layers = config["hybrid_override_pattern"].count("M")
    print(f"ssd_grouped_scan_roofline: {config['n_groups']} groups of B and "
          f"C; least {forward * 1e3:.4f} ms forward ({forward_bound}-bound) "
          f"+ {backward * 1e3:.4f} ms backward ({backward_bound}-bound) a "
          f"layer, {layers} layers; took {ms:.3f} ms a step", flush=True)
    return 100.0 * layers * (forward + backward) * 1e3 / ms
