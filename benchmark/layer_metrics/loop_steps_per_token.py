"""Layer: engine. How many times over a decode step ran its stack of
layers for a live row: the program's counters ``loop.layer_passes`` (live
rows x loop steps run x layers) over ``loop.rows_live`` x
``num_hidden_layers``, both summed over the window's decode steps.
``total_ut_steps`` (4.0) while every loop step is computed for every
row; a fall means loop steps were left out, which is another model and
not a faster one. The program adds to ``loop.layer_passes`` inside its
loop, where a layer is applied, so the reading follows what ran; it does
not see a layer applied to fewer rows than are live, which the served
logits' comparison guards. Nothing to read where the program banks no
such counters."""

from benchmark.harness.step_counters import steps


def read(record):
    passes = sum(steps(record, "loop.layer_passes"))
    rows = sum(steps(record, "loop.rows_live"))
    if not rows:
        return None
    return passes / (rows * record["config"]["num_hidden_layers"])
