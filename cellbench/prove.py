"""The readings a limit is set from, on the chip, at the cell's own size:
``python3 cellbench/prove.py --workload <cell> --seeds 12 --out <file>``.

One process, seed after seed (set-up is long and the programs are in the
cache after the first): for each seed a short window of the cell, then

  * ``program``   - the timed path against the reference, at the precision
                    the configuration states: the lower readings;
  * ``stand_in``  - the reference one precision lower, in the program's
                    place: the control;
  * ``faults``    - the reference with one fault planted, in the
                    program's place, for each fault the cell's kind
                    names (``FAULTS`` of ``kinds/<kind>.py``);

and, with ``--control-seeds n``, for the first n seeds the program itself
with its own lower-precision path switched on (``Program(control=True)``)
against the same reference.  Not part of a
benchmark run; PERF.md quotes what it printed.
"""

import argparse
import gc
import json
import sys

import run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        run.log("prove: needs a TPU")
        return 2
    _, cell, cfg, traffic = run.find_cell(args.workload)
    stated = cfg["precision"]["matmul"]
    with open(args.out, "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            out = run.run_cell(cell, cfg, traffic, seed, args.seconds, 0)
            row = {"cell": cell["name"], "seed": seed,
                   "program": out["numbers"],
                   "stand_in": out["numbers_fn"](
                       out["replayed"], stated,
                       stand_in=cfg["precision"]["control"])}
            faults = run.load_named("kinds", traffic["kind"]).FAULTS
            if faults:
                row["faults"] = {
                    fault: out["numbers_fn"](out["replayed"], stated,
                                             fault=fault)
                    for fault in faults}
            del out
            gc.collect()
            if i < args.control_seeds:
                out = run.run_cell(cell, cfg, traffic, seed, args.seconds,
                                   0, control=True)
                row["program_control"] = out["numbers"]
                del out
                gc.collect()
            f.write(json.dumps(row) + "\n")
            f.flush()
            run.log(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
