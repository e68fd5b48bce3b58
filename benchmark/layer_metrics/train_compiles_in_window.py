"""Jitted steps: the reader of ``serve_compiles_in_window`` under the training cells' name.  A
metric moves one end-to-end metric and only ``setup_s`` is in every cell, so
what is read in all four cells exists once a kind of cell."""

from benchmark.layer_metrics.serve_compiles_in_window import read  # noqa: F401
