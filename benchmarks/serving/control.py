"""Readings that set a cell's correctness limit: the program's widest gap
and the control's, on several seeds in one process.

    python3 benchmarks/serving/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 10

For each seed this runs the cell as ``run.py`` does (set-up, a window at the
cell's own load) and then, over the same checked requests, the plain
reference twice: as the judge of the served tokens (the program's reading),
and with float8 matmul operands as the control (at each row, the gap of the
token the float8 forward puts first).  Both go through the run's own
comparison against the cell's limit (``checks/<cell>.json``); each seed
prints both verdicts, and the control has to come out not correct.  The
benchmark's own runs never run the control.  The last line is a JSON
summary.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def readings(cell, seed: int, seconds: float, t_process: float, clog):
    from servbench import harness
    harness.device_info(cell.chips)
    out = harness.setup_and_serve(cell, seed, seconds, False, t_process, clog)
    gc.collect()
    checked = out["checked"]
    limit = cell.check["max_logit_gap"]
    row = {"seed": seed, "attempted": len(out["window"].due),
           "limit": limit}
    for side, control in (("program", False), ("control", True)):
        chk = harness.check_served(cell, seed, checked, control)
        gc.collect()
        row[side] = dict(chk, correct=harness.is_correct(chk, limit))
    return row


def _gaps(rows, side):
    return [r[side]["max_logit_gap"] for r in rows
            if r[side]["max_logit_gap"] is not None]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from servbench import harness
    cell = harness.Cell.load(args.workload)
    clog = harness.CompileLog()
    rows = []
    t = T_PROCESS
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            rows.append(readings(cell, seed, args.seconds, t, clog))
        except harness.NoAccelerator as e:
            print(f"no result: {e}", file=sys.stderr, flush=True)
            return 2
        r = rows[-1]
        print(f"seed {seed}: {r['attempted']} requests due; program "
              f"max_logit_gap {r['program']['max_logit_gap']} correct="
              f"{r['program']['correct']}; control max_logit_gap "
              f"{r['control']['max_logit_gap']} correct="
              f"{r['control']['correct']} (limit {r['limit']}, "
              f"{r['control']['tokens']} tokens)", flush=True)
        t = time.perf_counter()
    print(json.dumps({
        "workload": args.workload, "seeds": [r["seed"] for r in rows],
        "limit": cell.check["max_logit_gap"],
        "program_highest": max(_gaps(rows, "program"), default=None),
        "control_lowest": min(_gaps(rows, "control"), default=None),
        "program_correct": [r["program"]["correct"] for r in rows],
        "control_correct": [r["control"]["correct"] for r in rows]}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
