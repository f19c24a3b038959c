"""Device time of the Mamba-2 scan where ``B`` and ``C`` come in groups, a
step: ``ssd_scan_ms.py``'s reader over the same scope, under a name of this
cell's own (``tests/benchmark/test_benchmark_granite.py`` holds
``ssd_scan_ms`` to Granite's cell with ``==``)."""

import cells

read = cells.load_code(cells.HERE, "layer_metrics", "ssd_scan_ms.py").read
