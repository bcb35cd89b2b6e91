"""Layer: model step. Operations the forward and backward passes
REQUIRE per token (the family's function of the configuration's shapes;
recomputation not counted) x tokens a second over the steady log
intervals of this run (the median interval, so that the traced one does
not count) / (chips x the published bf16 peak)."""

import statistics

from benchmark.harness.device import share_pct


def read(record):
    counters, family = record["counters"], record["family"]
    if not hasattr(family, "train_flops_per_token"):
        return None
    intervals = record["spans"]["boundary_s"][1:]   # the first warms up
    if not intervals:
        return None
    tokens_per_s = counters["tokens_per_step"] * counters["log_every"] \
        / statistics.median(intervals)
    flops = family.train_flops_per_token(record["config"],
                                         record["traffic"]["seq_len"])
    return share_pct("train_mfu_pct", flops * tokens_per_s,
                     record["chips"] * record["peaks"]["bf16_flops_per_s"])
