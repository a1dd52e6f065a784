"""Run one benchmark cell on the chip this process finds.

    python3 benchmarks/serving/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  Set-up (JAX,
weights from the seed, the pool, warm-up, the context the traffic needs) is
timed as ``setup_s``; then the traffic is served for ``--seconds``.  With
``--trace 0`` the result line holds the cell's end-to-end metrics; with
``--trace 1`` a short traced sub-window follows and the line holds its
per-layer metrics.  Either way the served tokens are checked against the
plain reference, and the last lines on stderr give each compared number
beside its limit.  The last line on stdout is the JSON result.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from servbench import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    for name, v in result["check"].items():
        print(f"check {name} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
