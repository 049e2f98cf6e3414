"""On-chip benchmark of the serving engine, driven by data.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``).  Per-layer metrics are read by
``bench/layer_metrics/<metric>.py``, peaks come from ``bench/peaks.json``
and each cell's correctness limit from ``bench/limits/<cell>.json``.
"""
