"""Find the knee of an open-loop cell: the highest fixed rate it sustains.

    python perfbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 4,6,8

One process builds the cell once and runs one open-loop window per rate,
each drained before the next; it prints, per rate, the tails and whether
the window's requests were all served within the mix's drain limit.  The
knee is read off by hand and 0.8 of it written into the mix file as
``rate_per_s``.  Needs the chip, like ``run.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness, spec, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.profile import enable_compilation_cache

    enable_compilation_cache()
    cell = spec.find_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    sizes = []
    for r in rates:
        sizes += traffic.sizes(cell.mix, max(1, int(round(r * args.seconds))))
    sched = harness.build_scheduler(cell, args.seed, sizes)
    inst = harness.Instrument(sched)
    print(f"setup_s {time.perf_counter() - T_START:.1f}", flush=True)
    for r in rates:
        mix = dict(cell.mix, rate_per_s=r)
        res = harness.open_window(sched, inst, mix, args.seed, args.seconds, None)
        row = {"rate_per_s": r, **res["metrics"], **res["notes"], "failed": res["failed"]}
        print(json.dumps(row), flush=True)
        inst.steps.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
