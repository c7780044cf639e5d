"""Chip benchmark of the fused multi-tenant GEMM path.

Entry point: ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout, on a TPU.
``BENCHMARK.json`` at the root names the cells; each configuration, traffic
mix, per-layer metric reader and device's peaks is a file of its own under
this directory, found by its name.
"""
