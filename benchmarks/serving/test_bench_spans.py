"""The scheduler's own spans in a profiler trace, and the metric that reads
them: on hand-made host lines, and on a trace recorded here on the CPU of a
tiny model served through the harness (a trace of the host side only)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import harness, readers, spans  # noqa: E402
from servbench.xtrace import Reduced  # noqa: E402

CONF = dict(model="dense_elitekv",
            arch="tiny", hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=2, vocab_size=256, rope_theta=10000.0,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            elitekv=dict(elite_r=4, d_ckv=16, lrd="joint"),
            dtype="float32", pool_dtype="float32", block_size=16,
            num_blocks=64)
MIX = {"loop": "closed", "clients": 3,
       "prompt": {"kind": "uniform", "min": 8, "max": 40},
       "output": {"kind": "uniform", "min": 4, "max": 12},
       "steady_start": False, "set_size": 32,
       "scheduler": {"max_slots": 4, "max_len": 128,
                     "prefill_chunk_tokens": 16, "prefill_batch_lanes": 2,
                     "prefix_cache": False, "eviction": "recompute"}}


def _ctx(host):
    red = Reduced(window=(0.0, 100.0), ops=[], host=host)
    return harness.Ctx(dims=None, peaks={}, window=harness.Window(),
                       trace=red)


def test_self_seconds_of_nested_spans():
    host = [("sched.step", 0, 10), ("sched.grow", 1, 4),
            ("sched.swap", 2, 3), ("sched.decode", 5, 9),
            ("sched.step", 10, 12)]
    own = spans.self_seconds(host)
    assert own == pytest.approx({"sched.step": 3.0 + 2.0, "sched.grow": 2.0,
                                 "sched.swap": 1.0, "sched.decode": 4.0})


def test_step_host_ms_leaves_out_forward_self_time():
    # two steps of 10 and 20 s; forwards take 4 + 1 and 12 s of them, and a
    # pack nested inside the prefill span is host time
    host = [("bench.step", 0, 11), ("sched.step", 0, 10),
            ("sched.prefill", 1, 3), ("sched.pack", 1.5, 2.5),
            ("sched.decode", 4, 7), ("bench.record", 11, 12),
            ("sched.step", 12, 32), ("sched.verify", 13, 25),
            ("PjitFunction(_decode)", 4, 4.5)]
    fwd = (2 - 1) + 3 + 12
    assert spans.step_host_ms(_ctx(host)) == pytest.approx(
        (30 - fwd) * 1e3 / 2)


@pytest.mark.parametrize("host", [[], [("bench.step", 0, 1),
                                       ("sched.decode", 0.2, 0.8)]],
                         ids=["empty", "no_step_span"])
def test_step_host_ms_is_none_without_step_spans(host):
    assert spans.step_host_ms(_ctx(host)) is None


def test_step_host_ms_is_none_untraced():
    ctx = harness.Ctx(dims=None, peaks={}, window=harness.Window())
    assert spans.step_host_ms(ctx) is None


@pytest.fixture(scope="module")
def traced_cpu():
    """A tiny model served on the CPU for a short traced sub-window, as a
    ``--trace 1`` run serves one: → (traced Window, Reduced)."""
    from servbench import traffic
    cell = harness.Cell(name="tiny", chips=1, conf=CONF, mix=MIX, check={},
                        e2e=[], per_layer=[])
    params, buffers = cell.model.make(cell.dims, 3)
    sched = harness.build_scheduler(cell, params, buffers)
    server = harness.Server(cell, sched, traffic.Mix(MIX, cell.dims.vocab, 3))
    off = harness._annotator(False)
    initial = [server.send(p, b, c, harness.clock())
               for c, (p, b) in enumerate(server.mix.initial())]
    server.drain(off, lambda: all(r.done is not None for r in initial))
    return harness.traced_window(server, 1.0)


def test_trace_holds_steps_enclosing_their_phases(traced_cpu):
    traced, red = traced_cpu
    sched = [h for h in red.host if h[0].startswith("sched.")]
    steps = [h for h in sched if h[0] == "sched.step"]
    assert len(steps) == traced.steps > 0
    bench = [h for h in red.host if h[0] == "bench.step"]
    for _, s, e in steps:                  # inside the harness's own span
        assert any(bs <= s and e <= be for _, bs, be in bench)
    names = {h[0] for h in sched}
    assert {"sched.admit", "sched.grow", "sched.pack", "sched.prefill",
            "sched.decode", "sched.sample"} <= names
    for name, s, e in sched:
        if name != "sched.step":
            assert any(ss <= s and e <= se for _, ss, se in steps), name


def test_step_host_ms_agrees_with_the_harness_clock(traced_cpu):
    """The program's clock and the harness's clock around ``step()`` time
    the same steps; the forward phases are the same counters' spans."""
    traced, red = traced_cpu
    ctx = harness.Ctx(dims=None, peaks={}, window=traced, trace=red)
    program = spans.step_host_ms(ctx)
    outside = (traced.step_wall_s * 1e3 - sum(
        traced.delta(f"phase_{p}_ms") for p in readers.FORWARD_PHASES)
    ) / traced.steps
    assert 0 < program <= outside
    assert program == pytest.approx(outside, rel=0.05, abs=0.1)
