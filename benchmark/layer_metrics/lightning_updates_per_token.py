"""Layer: engine. How many lightning layers' states a decode step
advanced for a live row: the program's counters ``lin.state_updates``
(live rows, added to where a lightning layer's state advances) over
``lin.rows_live``, both summed over the window's decode steps. The
number of ``lightning-attn`` layers held (12.0) while every one of them
advances for every live row; a fall means a layer's recurrence was left
out, which is another model and not a faster one. It does not see a
state advanced wrongly, which the served logits' comparison guards.
Nothing to read where the program banks no such counters."""

from benchmark.harness.step_counters import steps


def read(record):
    updates = sum(steps(record, "lin.state_updates"))
    rows = sum(steps(record, "lin.rows_live"))
    return updates / rows if rows else None
