"""Find the highest arrival rate an open-loop cell sustains.

    python3 benchmarks/serving/sweep.py --workload granite2b.chat \
        --rates 2,3,4,5 --seconds 30 --seed 1

One process: the cell's set-up once, then for each rate, in the order given,
a window of the cell's traffic at that rate, each starting from the load the
one before left (give the rates rising).  Per rate it prints the queue
length and the busy slots at each quarter of the window and TTFT
percentiles of the requests due in each half; a backlog that grows through
the window means the rate is above what the system sustains.  The cell's traffic file then
states a fixed rate below that; the benchmark's own runs never sweep.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np
    from repro.launch import compile_cache
    from servbench import harness, traffic

    cell = harness.Cell.load(args.workload)
    harness.device_info(cell.chips)
    compile_cache.enable()
    params, buffers = cell.model.make(cell.dims, args.seed)
    sched = harness.build_scheduler(cell, params, buffers)
    del params, buffers
    off = harness._annotator(False)
    prev = None
    warm = harness.Server(cell, sched, traffic.Mix(cell.mix, cell.dims.vocab,
                                                  args.seed))
    harness.warm_up(warm, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix_spec = copy.deepcopy(cell.mix)
        mix_spec["rate_per_s"] = rate
        server = harness.Server(cell, sched, traffic.Mix(
            mix_spec, cell.dims.vocab, args.seed))
        prior = prev or warm
        server.uid, server.n_finished = prior.uid, prior.n_finished
        server.recs = prior.recs
        queue, busy, windows = [], [], []
        for _ in range(4):
            windows.append(server.serve(args.seconds / 4, off))
            queue.append(len(sched.waiting))
            busy.append(server.lanes())
        ttft = [[r.first - r.due for r in w.due if r.first is not None]
                for w in windows]
        half = [ttft[0] + ttft[1], ttft[2] + ttft[3]]
        pct = lambda xs, q: float(np.percentile(xs, q)) * 1e3 if xs else None
        print(json.dumps({
            "rate": rate, "due": sum(len(w.due) for w in windows),
            "tokens_per_s": sum(w.tokens for w in windows)
            / sum(w.seconds for w in windows),
            "queue_at_quarters": queue, "busy_slots_at_quarters": busy,
            "ttft_p50_ms_halves": [pct(h, 50) for h in half],
            "ttft_p90_ms_halves": [pct(h, 90) for h in half],
            "itl_p95_ms": pct(sum((w.gaps for w in windows), []), 95)}),
            flush=True)
        prev = server
    return 0


if __name__ == "__main__":
    sys.exit(main())
