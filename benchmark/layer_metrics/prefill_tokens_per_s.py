"""Layer: engine. How fast a prompt becomes cached rows and a first
token: median, over the requests prefilled inside the window, of the
prompt's length over the seconds of its four prefill spans
(``serve.prefill.build`` + ``.dispatch`` + ``.scatter`` + ``.wait``,
matched by their ``request_id``; ``build`` carries ``prompt_len``). A
prompt is prefilled whole in the step that admits it. Of the judged
metrics it moves ``setup_s``: the warm-up prefills one prompt of every
distinct length and ``max_batch`` of the shortest, more than half of a
warm set-up where prompts are long; the 95th percentile of the token
gap is a decode-only step while fewer than 5% of gaps hold a prefill.
Nothing to read where the program exports no such spans."""

import statistics

from benchmark.harness.program_spans import exported, serve_window

STAGES = ("serve.prefill.build", "serve.prefill.dispatch",
          "serve.prefill.scatter", "serve.prefill.wait")


def read(record):
    times, window = exported("serve"), serve_window(record)
    if times is None or window is None:
        return None
    seconds, length = {}, {}
    for stage in STAGES:
        for s in times.samples(stage, *window):
            rid = s.attrs.get("request_id")
            seconds.setdefault(rid, {})[stage] = s.seconds
            if "prompt_len" in s.attrs:
                length[rid] = s.attrs["prompt_len"]
    rates = [length[rid] / sum(parts.values())
             for rid, parts in seconds.items()
             if rid in length and len(parts) == len(STAGES)]
    return statistics.median(rates) if rates else None
