"""Record the small profiler trace that the trace-reduction tests read.

    python3 benchmarks/serving/record_testdata.py --out <dir>

On a TPU: serves a two-layer EliteKV model (Granite widths per head, tiny
depth and vocabulary) through the same ``Scheduler`` and Pallas decode
kernel the cells use, for a fraction of a second under the profiler, with
the benchmark's ``bench.*`` host spans.  Writes ``<dir>/small.xplane.pb``
and prints each plane's lines with their event counts and time ranges, the
names of the busiest device operations, and the reduction's numbers, which
the tests pin.
"""
import argparse
import collections
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

CONF = dict(model="dense_elitekv",
            arch="granite_3_2b", hidden_size=512, intermediate_size=1024,
            num_attention_heads=8, num_key_value_heads=2,
            num_hidden_layers=2, vocab_size=1024, rope_theta=10000.0,
            rms_norm_eps=1e-5, tie_word_embeddings=True,
            elitekv=dict(elite_r=8, d_ckv=128, lrd="joint"),
            dtype="float32", pool_dtype="float32", block_size=16,
            num_blocks=512)
MIX = {"loop": "closed", "clients": 8,
       "prompt": {"kind": "uniform", "min": 64, "max": 256},
       "output": {"kind": "uniform", "min": 16, "max": 64},
       "steady_start": True, "set_size": 64,
       "scheduler": {"max_slots": 8, "max_len": 512,
                     "prefill_chunk_tokens": 128, "prefill_batch_lanes": 4,
                     "prefix_cache": False, "eviction": "recompute"}}


def dump(path: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            names = collections.Counter(e.name for e in evs)
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"[{lo}, {hi}] ns, top {names.most_common(6)}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    args = ap.parse_args()
    from servbench import harness, traffic, xtrace
    cell = harness.Cell(name="testdata", chips=1, conf=CONF, mix=MIX,
                        check={}, e2e=[], per_layer=[])
    harness.device_info(1)
    params, buffers = cell.model.make(cell.dims, 1)
    system = harness.build_system(cell, params, buffers)
    server = harness.Server(cell, system, traffic.Mix(MIX, cell.dims.vocab,
                                                      1))
    off = harness._annotator(False)
    initial = [server.send(p, b, c, harness.clock())
               for c, (p, b) in enumerate(server.mix.initial())]
    server.drain(off, lambda: all(r.first is not None for r in initial))
    server.serve(1.0, off)                         # warm every program
    tmp = tempfile.mkdtemp()
    try:
        traced, _ = harness.traced_window(server, args.seconds, keep_in=tmp)
    except ValueError as e:                        # no window span found
        print(f"reduction failed: {e}")
        traced = None
    files = [os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
             if f.endswith(".xplane.pb")]
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "small.xplane.pb")
    shutil.copy(files[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {dst}: {os.path.getsize(dst)} bytes")
    dump(dst)
    if traced is None:
        return 1
    red = xtrace.reduce(dst)
    print(json.dumps({
        "window_s": red.window_s, "busy_s": red.busy_s(),
        "idle_share": red.idle_share(),
        "decode_kernel_s": red.op_seconds("elite_decode_paged"),
        "top_ops": red.top_ops(10), "idle_by_host": red.idle_by_host(10),
        "decode_steps": len(traced.decode_lengths),
        "decode_lengths": traced.decode_lengths}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
