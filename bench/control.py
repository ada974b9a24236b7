"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--program]
        [--rows control_fp8 half_batch ...] [--out PATH]

For each seed, against the float32 reference of the first steps: the
reference in float8 put in the program's place (the control), and the
reference with a fault planted in it: half of every batch left out, the
embedding's update left out, the second moment of the full-shape Adam
leaves read four times too large. With --program the program's own first
steps (a window of none) are read too, from the same process. Prints one
JSON line per seed: each number of bench/check.py, and the verdict of each
row under the committed limits (bench/limits/<cell>.json). --rows reads only
the rows it names. With --out, also appends per-leaf norms of the program
and the reference, and each row's per-leaf `grad_elem` gaps, to PATH. The limits
are set from these readings (lower: the program's largest over seeds;
upper: the least the control or a fault gives). The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

ROWS = {  # row name -> reference_readings arguments besides the batches
    "control_fp8": {"precision": "fp8"},
    "half_batch": {"rows": "half"},
    "embed_update_skipped": {"fault": "embed_update_skipped"},
    "v_scale_x4": {"fault": "v_scale_x4"},
}


def _row(nums: dict, lim: dict) -> dict:
    from bench import check

    ok, _ = check.verdict(nums, lim)
    return {"correct": ok, **{k: [v, at] for k, (v, at) in nums.items()}}


def _leaves(readings: dict, keys) -> dict:
    return {f"{p}[{l}]": readings["change_norms"][(p, l)] for p, l in keys}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--rows", nargs="*", default=list(ROWS), choices=list(ROWS),
                    help="which of the control and the faults to read (all)")
    ap.add_argument("--out", default=None, metavar="PATH")
    args = ap.parse_args(argv)

    from bench import check, run
    from bench.cell import load_cell
    from bench.data import TokenStream

    cell = load_cell(args.workload)
    lim = check.limits(cell.name)
    run.setup_jax()
    import jax

    devices = run.require_chips(cell.chips)
    for seed in args.seeds:
        row = {"seed": seed}
        if args.program:
            prog = run.drive(cell, seed, 0.0, False, devices)
            batches = prog.pop("batches")
        else:
            stream = TokenStream(cell.traffic, cell.config["vocab_size"],
                                 cell.rows, seed)
            batches = [stream.batch(s) for s in range(run.CHECK_STEPS)]
        ref = run.reference_readings(cell, seed, batches)
        # to the host: each row below runs a whole reference of its own, which
        # needs the chip's memory as the benchmark's single reference has it
        ref["first_grads"] = jax.device_get(ref["first_grads"])
        if args.program:
            row["program"] = _row(check.numbers(prog, ref), lim)
        elem = {}
        if args.program:
            elem["program"] = check.grad_elem_gaps(prog, ref)
        for name in args.rows:
            kw = {k: (cell.rows // 2 if v == "half" else v)
                  for k, v in ROWS[name].items()}
            other = run.reference_readings(cell, seed, batches, **kw)
            row[name] = _row(check.numbers(other, ref), lim)
            elem[name] = check.grad_elem_gaps(other, ref)
            del other
            gc.collect()
        print(json.dumps(row), flush=True)
        if args.out:
            grp = check.groups(ref)
            detail = {"seed": seed, "groups": {k: [f"{p}[{l}]" for p, l in v]
                                               for k, v in grp.items()},
                      "reference": _leaves(ref, sum(grp.values(), [])),
                      "grad_elem": {name: {f"{p}[{l}]": v for (p, l), v in g.items()}
                                    for name, g in elem.items()},
                      "first_step_rms": {f"{p}[{l}]": v for (p, l), v
                                         in ref["first_step_rms"].items()},
                      "w0_half_ulp_rms": {f"{p}[{l}]": v for (p, l), v
                                          in ref["w0_half_ulp_rms"].items()}}
            if args.program:
                detail["program"] = _leaves(prog, sum(grp.values(), []))
                detail["program_losses"] = prog["losses"]
                detail["reference_losses"] = ref["losses"]
            with open(args.out, "a") as f:
                f.write(json.dumps(detail) + "\n")
        # nothing of this seed stays on the chip while the next one runs
        prog = ref = None
        gc.collect()


if __name__ == "__main__":
    main()
