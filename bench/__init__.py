"""Chip benchmark: serving cells driven by ``BENCHMARK.json``.

One run measures one cell (a model configuration under a traffic mix):

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

    bench/configs/<config>.json   sizes as run, source, cuts, serving settings
    bench/traffic/<mix>.json      arrival process and length distributions
    bench/metrics/<metric>.py     reader of one per-layer metric
    bench/limits/<cell>.json      the correctness limit and its readings

The yardstick lives here too: the plain float32 reference
(``reference.py``), the weights drawn from the seed (``weights.py``), the
traffic generator (``traffic/gen.py``), the peaks table (``peaks.py``),
the FLOP and byte counts (``flops.py``) and the trace reduction
(``trace_reduce.py``).
"""
