"""Readings that a cell's correctness limit is set from.

    python perfbench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: build the cell, run its window at its own
load for ``--seconds``, take the same sample of finished requests as a run
does, free the program's state, and read over the sample's prompts and
served tokens

* ``program``: the widest gap by which a served token's float32 reference
  logit lies below the reference's best (what a run compares);
* ``control``: the same for the token that the reference computed at
  ``check.control_bits`` (the next precision below the configuration's
  int8 datapath) puts first at each position.

The limit in the configuration file lies between the largest ``program``
reading of sound runs and the smallest ``control`` reading.  Needs the
chip, like ``run.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from perfbench import harness, spec  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    """Program and control widest gaps of one seed's run of ``cell``."""
    import jax

    from perfbench import weights

    sched = harness.build_scheduler(cell, seed, harness.pool_sizes(cell.mix, seconds))
    inst = harness.Instrument(sched)
    window = harness.closed_window if cell.mix["loop"] == "closed" else harness.open_window
    check = cell.config["check"]
    res = window(sched, inst, cell.mix, seed, seconds, None, check["served_tokens"])
    sample = harness.check_sample(
        res["requests"], seed, check["served_tokens"], check["max_requests"]
    )
    del sched, inst, res
    gc.collect()
    w = weights.published_weights(cell.arch, cell.config["sizes"], seed)
    out = {"seed": seed, "requests": len(sample)}
    for key, bits in (("program", None), ("control", check["control_bits"])):
        gaps = np.concatenate(
            [
                cell.arch.served_gaps(w, p, s, cell.config["sizes"], control_bits=bits)
                for p, s, _ in sample
            ]
        )
        out[key] = float(gaps.max())
        out[f"{key}_median"] = float(np.median(gaps))
    out["served"] = int(sum(len(s) for _, s, _ in sample))
    del w
    jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.profile import enable_compilation_cache

    enable_compilation_cache()
    cell = spec.find_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
