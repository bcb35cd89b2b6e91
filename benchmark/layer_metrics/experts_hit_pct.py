"""Layer: engine. Of the routed experts this chip holds (held experts x
expert layers), the share at least one token of a decode step was routed
to: the program's counter ``moe.experts_hit``, median over the window's
decode steps. An expert no token hits need not be read. Nothing to read
where the program banks no such counter."""

from benchmark.harness.program_counters import median, window_counts


def read(record):
    config = record["config"]
    hit = median(window_counts(record, "moe.experts_hit"))
    if hit is None or "held_experts" not in config:
        return None
    held = len(config["held_experts"]) * (
        config["num_hidden_layers"] - config["first_k_dense_replace"])
    return 100.0 * hit / held
