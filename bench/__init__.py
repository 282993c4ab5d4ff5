"""Chip benchmark of Shark's SQL and SQL->ML paths.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` on the TPU it is started on.  Each
configuration (`configs/`), traffic mix (`traffic/`) and per-layer metric
(`metrics/`) is a file of its own that the harness finds by the name in
`BENCHMARK.json`; the shared code here never names a cell.
"""
