"""Device time of a Mamba-2 mixer with grouped ``B``, ``C`` and norm beside
its projections and the scan, a step: ``ssd_mix_ms.py``'s reader over the
same scopes, under a name of this cell's own (Granite's test holds
``ssd_mix_ms`` to Granite's cell with ``==``)."""

import cells

read = cells.load_code(cells.HERE, "layer_metrics", "ssd_mix_ms.py").read
