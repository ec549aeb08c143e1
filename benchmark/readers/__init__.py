"""One module per KIND of reader. Each has
``read(params: dict, run: common.RunResult) -> float | None``: the
metric's value, or None where this run holds nothing to read (the
harness then leaves the metric out of the line). A per-layer metric is
a JSON file under ``layer_metrics/`` that names a reader and gives its
parameters; a new metric over an existing reader is a new JSON file."""
