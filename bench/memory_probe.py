"""Device memory through the window of one benchmark run, call by call.

    python3 bench/memory_probe.py --workload <cell> --seed <n> --seconds <s> \
        [--driver <checkout>/bench/drivers/fixpoint.py]

Sets the cell's driver up as ``bench/run.py`` does, runs its window for
``--seconds`` and reads the first chip's ``bytes_in_use`` and
``peak_bytes_in_use`` after set-up, after each windowed call, after the
window and after ``release()``; then compares the window's answers with
the reference as a run does.  ``--driver`` runs another checkout's driver
(a parent's) under this harness, so one probe reads both sides.  Where a
driver keeps each call's answer on the chip, bytes in use grow by one
answer a call and the peak follows once they pass set-up's; where it
copies each answer to the host, they stay flat.

Prints one JSON line.  Exits 2 without a TPU: the CPU reports no memory.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def memory(device) -> list:
    """[bytes_in_use, peak_bytes_in_use]; None where the device has no
    counts."""
    stats = device.memory_stats() or {}
    return [stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")]


def probe(driver, device, seconds: float) -> dict:
    """Memory of one run of ``driver`` on ``device``, step by step."""
    from bench.drivers.common import Spans

    after_each_call = []

    class CallSpans(Spans):
        @contextlib.contextmanager
        def __call__(self, name):
            with super().__call__(name):
                yield
            after_each_call.append(memory(device))

    out = dict(start=memory(device))
    driver.setup()
    out["setup"] = memory(device)
    measured = driver.window(seconds, CallSpans())
    out.update(window=memory(device), calls=driver.attempted(),
               after_each_call=after_each_call, **measured)
    driver.release()
    out["release"] = memory(device)
    out["pr_max_abs_err"] = max(driver.compare()["pr_max_abs_err"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--driver", help="a driver file to run in place of "
                    "the cell's own")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    workload, config = run.load_cell(args.workload)
    try:
        devices, _ = run.chips(config["chips"])
    except run.NoChip as e:
        print(f"memory_probe: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    path = args.driver or os.path.join(BENCH, "drivers",
                                       workload["driver"] + ".py")
    driver = run.load_module(os.path.abspath(path)).Driver(
        config, workload, args.seed, devices)
    out = dict(workload=args.workload, seed=args.seed, driver=path,
               **probe(driver, devices[0], args.seconds))
    out["limit"] = workload["limits"]["pr_max_abs_err"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
