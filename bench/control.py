"""Readings of the control: the plain reference computed in bfloat16, put in
the program's place, at a cell's own size, on the seeds given.

    python bench/control.py --workload <name> --seeds 1 2 3 [--epochs N]

It reads every gap check up to ``--epochs`` (default: the epochs a whole
solve of the float64 reference needs to reach the cell's target) and
prints one JSON line per seed with the three compared numbers.  The
benchmark's own runs do not run it; its readings set the upper end of
each limit (``bench/limits/<cell>.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def epochs_to_target(csr, cfg, traffic, cap: int) -> int:
    from bench import check, objective

    ref = check._reference(csr, cfg, traffic, "float64")
    gap = objective.HostGap(csr, cfg["loss"], cfg["lam"])
    while ref.epochs < cap:
        ref.epoch()
        if gap(ref.w, ref.alpha) <= cfg["gap_target"]:
            break
    return ref.epochs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--epochs", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import check, gen
    from bench.run import load_cell

    _, _, cfg, traffic, limits = load_cell(ROOT, args.workload)
    base = gen.powerlaw_csr(cfg["m"], cfg["d"], cfg["nnz_per_row"],
                            cfg["alpha"], cfg["data_seed"])
    for seed in args.seeds:
        t = time.perf_counter()
        csr = gen.permute_rows(base, seed, int(cfg["p"]))
        epochs = args.epochs or epochs_to_target(csr, cfg, traffic,
                                                 int(traffic["epoch_cap"]))
        got = check.control_readings(csr, cfg, traffic, epochs)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "epochs": epochs, "control": got,
                          "fails": [k for k, v, lim, ok in
                                    check.judge(got, limits) if not ok],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path = [p for p in sys.path if os.path.abspath(p) != HERE]
    sys.exit(main())
