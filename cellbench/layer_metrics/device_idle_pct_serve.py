"""Share of the traced window in which no operation ran on the device: the
same reading as the training cells', under the name that moves
`serve_tokens_per_s`."""

from cellbench.layer_metrics.device_idle_pct_train import read  # noqa: F401
