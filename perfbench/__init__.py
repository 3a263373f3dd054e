"""The benchmark of vantage6-tpu: one cell per process, driven by data files.

`BENCHMARK.json` at the root of the repository names the cells; everything a
cell, a configuration, a traffic mix or a metric needs lives in a file of its
own under this directory, found by its name (see README.md).
"""
