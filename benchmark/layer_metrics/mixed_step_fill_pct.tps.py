"""``mixed_step_fill_pct`` (the same reader, see it) for a cell at saturation, whose end-to-end metric it moves is the tokens a second (serve_out_tokens_per_s) and not the time to first token."""

from benchmark.layer_metrics.mixed_step_fill_pct import read  # noqa: F401
