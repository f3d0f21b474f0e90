"""Whole training step against the chip's peak: model FLOPs a token (from
cellbench/counts, no recompute) x tokens/s of the run / (chips x peak FLOP/s)."""

from cellbench import harness


def read(observed):
    peaks, c = observed.get("peaks"), observed["counters"]
    if not peaks or not c.get("tokens"):
        return None
    counts = harness.find("counts", observed["config"]["family"])
    flops = counts.train_flops_per_token(observed["config"], c["seq"]) * c["tokens"]
    return 100.0 * flops / observed["window_s"] / (
        observed["chips"] * peaks["flops_per_s"])
