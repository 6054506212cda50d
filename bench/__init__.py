"""Chip benchmark: serving cells driven by ``BENCHMARK.json``.

One run measures one cell (a model configuration under a traffic mix):

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

    bench/configs/<config>.json   sizes as run, source, cuts, serving
                                  settings, and the family it belongs to
    bench/families/<family>.py    the model code of one family: ``arch``
                                  (the program's ArchConfig), ``make_params``
                                  (weights from the seed), ``gaps`` (the
                                  float32 reference and its control) and
                                  ``counts`` (FLOPs and bytes)
    bench/traffic/<mix>.json      arrival process and length distributions
    bench/metrics/<metric>.py     reader of one per-layer metric
    bench/limits/<cell>.json      the correctness limit and its readings

The yardstick lives here too: the traffic generator (``traffic/gen.py``),
the peaks table (``peaks.py``), the trace reduction (``trace_reduce.py``),
and the dense GQA code that ``families/dense_gqa.py`` uses: the plain
float32 reference (``reference.py``), the weights drawn from the seed
(``weights.py``) and the FLOP and byte counts (``flops.py``).
"""
