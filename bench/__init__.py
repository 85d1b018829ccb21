"""On-chip benchmark of federated SCBF training (see PERF.md).

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell named in ``BENCHMARK.json``.
"""
