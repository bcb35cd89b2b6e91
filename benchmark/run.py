"""python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` in a traced run). Exits with another code than 0 and
prints no result line unless ``jax.devices()`` are TPU chips, exactly as
many as the cell asks for: there is no CPU fallback.
"""

from __future__ import annotations

import time

CLOCK0 = time.perf_counter()     # set-up is counted from here

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_BAD_FILES = 2
EXIT_NO_CHIP = 3
EXIT_FAILED = 4


def run_cell(cell, seed: int, seconds: float, trace: bool, device_block,
             peaks, say, clock0: float):
    """Everything after the look for a chip: the cell's driver, then the
    line. Tests call this with a device block of their own."""
    from benchmark.harness import device, result
    from benchmark.harness.loader import load_part

    driver = load_part(cell, "drivers", cell.kind)
    record = driver.run(cell, seed, seconds, trace, clock0, device_block,
                        peaks, say)
    record.update(config=cell.config, traffic=cell.traffic, peaks=peaks,
                  chips=device_block["count"])
    say("memory", stats=device.memory_stats_all())
    for check in record["checks"]:
        print(check.line() + say.tag, flush=True)
    return result.build_line(cell, record, trace, device_block)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import device, loader, result

    try:
        cell = loader.load_cell(args.workload)
    except (loader.BenchmarkFileError, KeyError) as e:
        print("benchmark/run.py: %s" % e, file=sys.stderr)
        return EXIT_BAD_FILES
    try:
        device_block = device.require_chips(cell.chips)
        peaks = device.peaks_of(device_block["kind"])
    except (device.NoAccelerator, device.UnknownDevice) as e:
        print("benchmark/run.py: %s" % e, file=sys.stderr)
        return EXIT_NO_CHIP

    # the program's own switch: JAX_COMPILATION_CACHE_DIR where it is
    # set, else .compile_cache/ inside this checkout — a fixed path
    from paddle_operator_tpu import compile_cache

    compile_cache.enable_persistent_cache()
    say = result.say_factory("")
    say("start", cell=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device_block,
        cache_dir=compile_cache.default_cache_dir())
    say("self_check", **device.matmul_self_check(peaks))
    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        device_block, peaks, say, CLOCK0)
    except device.ShareOverPeak as e:
        print("benchmark/run.py: %s" % e, file=sys.stderr)
        return EXIT_FAILED
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
