"""Layer: engine. Device time of one run of the decode step over the
traced tail: seconds / runs of the XLA module the program names
``serve_decode`` (``jit_serve_decode(...)`` on the ``XLA Modules``
line). Nothing to read where no module has that name."""

MODULE = "jit_serve_decode("


def read(record):
    trace = record.get("trace")
    if trace is None:
        return None
    runs = seconds = 0
    for name, module in trace["modules"].items():
        if name.startswith(MODULE):
            runs += module["runs"]
            seconds += module["seconds"]
    return 1e3 * seconds / runs if runs else None
