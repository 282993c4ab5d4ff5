"""Per-layer metric readers: one module per metric of BENCHMARK.json's
`per_layer` list, named after it.  Each defines `read(run)` and returns
the metric's value, or None where the run holds nothing to read (then the
harness leaves the metric out of the line).  `run` is a `run.RunRecord`."""
