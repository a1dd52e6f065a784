"""Observability layer: tracer/metrics/timeline units, artifact validity,
and the golden invariant — instrumentation never changes what the scheduler
computes (traced and untraced runs are token-identical).

The exported artifacts are validated with the same ``tools/check_trace.py``
CI runs, loaded by path (tools/ is not a package), so the test suite and the
CI job can never drift on what "valid" means.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, MetricsRegistry, Tracer, to_chrome_trace,
                       write_chrome_trace)
from repro.runtime import serve_loop

_CHECK = Path(__file__).resolve().parent.parent / "tools" / "check_trace.py"


@pytest.fixture(scope="module")
def check_trace_mod():
    spec = importlib.util.spec_from_file_location("check_trace", _CHECK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------

def test_tracer_ring_drops_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert [e.name for e in evs] == ["e6", "e7", "e8", "e9"]
    assert tr.emitted == 10 and tr.dropped == 6
    assert [e.name for e in tr.last(2)] == ["e8", "e9"]


def test_tracer_span_times_and_nests_args():
    tr = Tracer()
    with tr.span("work", track="pool", cat="swap", shape="(2,3)"):
        pass
    (ev,) = tr.events()
    assert ev.ph == "X" and ev.dur >= 0.0 and ev.track == "pool"
    assert ev.arg("shape") == "(2,3)"
    assert ev.args_dict() == {"shape": "(2,3)"}


def test_disabled_tracer_is_inert():
    before = NULL_TRACER.emitted
    NULL_TRACER.instant("x")
    with NULL_TRACER.span("y"):
        pass
    assert NULL_TRACER.emitted == before and NULL_TRACER.events() == []
    assert "disabled" in NULL_TRACER.format_tail(5)


def test_format_tail_mentions_recent_events():
    tr = Tracer()
    tr.instant("admit", uid=7)
    tail = tr.format_tail(5)
    assert "admit" in tail and "uid" in tail


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_structure_and_track_order():
    tr = Tracer()
    tr.begin("req0", track="slot0", cat="request")
    tr.instant("alloc", track="pool")
    tr.counter("pool_blocks_used", 3, track="pool")
    with tr.span("decode", track="scheduler", cat="phase"):
        pass
    tr.end("req0", track="slot0")
    doc = to_chrome_trace(tr)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    names = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    # pinned tracks take the low tids in fixed order; slots follow
    assert names["scheduler"] == 0 and names["pool"] == 1 \
        and names["slot0"] == 2 and "kernel" not in names
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "B", "E", "i", "C", "X"} <= phs


def test_chrome_export_passes_checker(tmp_path, check_trace_mod):
    tr = Tracer()
    with tr.span("prefill", track="scheduler", cat="phase", tokens=8):
        pass
    tr.counter("pool_blocks_used", np.int64(5), track="pool")  # numpy coerces
    path = write_chrome_trace(tmp_path / "t.json", tr)
    assert check_trace_mod.main([str(path)]) == 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_instruments_and_export(tmp_path, check_trace_mod):
    m = MetricsRegistry()
    m.counter("reqs_total", "requests").inc(3)
    m.gauge("slots").set(2)
    h = m.histogram("step_ms", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.cumulative() == [1, 2, 3]      # cumulative le-buckets, +Inf last
    # same name returns the same instrument; a kind clash is an error
    assert m.counter("reqs_total") is m.get("reqs_total")
    with pytest.raises(AssertionError):
        m.gauge("reqs_total")
    txt = m.to_prometheus()
    assert '# TYPE step_ms histogram' in txt
    assert 'step_ms_bucket{le="+Inf"} 3' in txt
    p = tmp_path / "m.prom"
    p.write_text(txt)
    t = tmp_path / "empty.json"
    t.write_text(json.dumps({"traceEvents": []}))
    assert check_trace_mod.main([str(t), "--metrics", str(p)]) == 0
    js = m.to_json()
    assert js["step_ms"]["count"] == 3 and js["reqs_total"]["value"] == 3


def test_counter_rejects_decrease():
    with pytest.raises(AssertionError):
        MetricsRegistry().counter("x").inc(-1)


# ---------------------------------------------------------------------------
# golden invariant: tracing never perturbs the run
# ---------------------------------------------------------------------------

def _reqs(cfg, n=4, temp=0.8):
    rng = np.random.default_rng(5)
    return [serve_loop.Request(
        uid=i,
        prompt=rng.integers(0, cfg.vocab_size,
                            int(rng.integers(6, 16))).astype(np.int32),
        max_new_tokens=int(rng.integers(4, 9)), arrival=i * 0.7,
        temperature=temp, top_p=0.9, seed=31 + i) for i in range(n)]


def _scfg(num_blocks=10):
    return serve_loop.SchedulerConfig(
        max_slots=2, block_size=4, num_blocks=num_blocks, max_len=32,
        prefill_bucket=4, prefill_chunk_tokens=4, eviction="swap")


def test_traced_run_tokens_bit_identical(tiny_elite_cfg, tiny_elite_model,
                                         tmp_path, check_trace_mod,
                                         stress_blocks):
    """The acceptance gate: a fully traced + metered sampled run (tiny pool,
    preemption pressure) produces the exact token streams of an untraced
    run, and the artifacts it writes validate."""
    params, buffers = tiny_elite_model
    tr, metrics = Tracer(), MetricsRegistry()
    nb = stress_blocks(10)
    s1 = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(nb),
                              tracer=tr, metrics=metrics)
    rep1 = s1.run(_reqs(tiny_elite_cfg))
    s2 = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(nb))
    s2.run(_reqs(tiny_elite_cfg))
    assert {r.uid: list(r.generated) for r in s1.finished} == \
        {r.uid: list(r.generated) for r in s2.finished}

    assert rep1.trace_events == tr.emitted > 0
    lifecycle = [e.name for e in tr.events()]
    for name in ("submit", "admit", "first_token", "retire"):
        assert name in lifecycle
    tp = write_chrome_trace(tmp_path / "t.json", tr)
    mp = tmp_path / "m.prom"
    mp.write_text(metrics.to_prometheus())
    assert check_trace_mod.main([str(tp), "--metrics", str(mp)]) == 0
    assert metrics.get("serve_requests_completed_total").value == 4
    assert metrics.get("serve_tokens_decoded_total").value == \
        sum(len(r.generated) for r in s1.finished)


def test_swap_nests_in_grow_or_admit_counted_once(tiny_elite_cfg,
                                                  tiny_elite_model):
    """Under swap eviction a swap-out runs inside chain growth and a
    swap-in inside admission: every ``swap`` phase span sits inside a
    ``grow`` or ``admit`` span of its step, the enclosing phase is charged
    only its own time, and the self times still sum to the step wall."""
    params, buffers = tiny_elite_model
    tr = Tracer()
    s = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(6),
                             tracer=tr)
    rep = s.run(_reqs(tiny_elite_cfg))
    assert rep.swap_outs > 0 and rep.swap_ins > 0
    assert rep.phase_ms["swap"] > 0.0
    wall = rep.step_wall_ms_total
    assert abs(sum(rep.phase_ms.values()) - wall) <= 0.02 * wall

    spans = [e for e in tr.events() if e.ph == "X" and e.track == "scheduler"]

    def inside(e, name):
        return [o for o in spans if o.name == name and o.ts <= e.ts
                and e.ts + e.dur <= o.ts + o.dur]

    swaps = [e for e in spans if e.name == "swap"]
    assert {e.arg("direction") for e in swaps} == {"in", "out"}
    for e in swaps:
        (parent,) = inside(e, "admit" if e.arg("direction") == "in"
                           else "grow")
        assert inside(parent, "step")
    for name in ("admit", "grow"):
        total = sum(e.dur for e in spans if e.name == name) * 1e3
        nested = sum(e.dur for e in swaps if inside(e, name)) * 1e3
        # the phase's self time: its spans less the swaps inside them
        assert rep.phase_ms[name] == pytest.approx(total - nested,
                                                   rel=0.05, abs=0.5)


def test_untraced_scheduler_emits_nothing(tiny_elite_cfg, tiny_elite_model):
    params, buffers = tiny_elite_model
    s = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(64))
    rep = s.run(_reqs(tiny_elite_cfg, n=2, temp=0.0))
    assert rep.trace_events == 0 and rep.trace_dropped == 0
    assert s.trace is NULL_TRACER and not s.trace.events()


# ---------------------------------------------------------------------------
# stuck-scheduler diagnostics (satellite bugfix)
# ---------------------------------------------------------------------------

def test_did_not_drain_error_carries_diagnostics(tiny_elite_cfg,
                                                 tiny_elite_model):
    params, buffers = tiny_elite_model
    tr = Tracer()
    s = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(64),
                             tracer=tr)
    with pytest.raises(RuntimeError) as ei:
        s.run(_reqs(tiny_elite_cfg, n=3, temp=0.0), max_steps=1)
    msg = str(ei.value)
    assert msg.startswith("scheduler did not drain in 1 steps")
    assert "uid=" in msg                    # per-request status lines
    assert "pool:" in msg                   # pool usage line
    assert "dropped from the ring" in msg   # tracer tail header attached
    assert "submit" in msg


def test_did_not_drain_without_tracer_still_reports_requests(
        tiny_elite_cfg, tiny_elite_model):
    params, buffers = tiny_elite_model
    s = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(64))
    with pytest.raises(RuntimeError) as ei:
        s.run(_reqs(tiny_elite_cfg, n=2, temp=0.0), max_steps=1)
    msg = str(ei.value)
    assert "uid=" in msg and "tracing disabled" in msg


# ---------------------------------------------------------------------------
# trace-summary CLI smoke
# ---------------------------------------------------------------------------

def test_trace_summary_cli(tiny_elite_cfg, tiny_elite_model, tmp_path,
                           capsys):
    from repro.launch import diagnose
    params, buffers = tiny_elite_model
    tr = Tracer()
    s = serve_loop.Scheduler(params, buffers, tiny_elite_cfg, _scfg(64),
                             tracer=tr)
    s.run(_reqs(tiny_elite_cfg, n=2, temp=0.0))
    path = write_chrome_trace(tmp_path / "t.json", tr)
    diagnose.main(["trace-summary", str(path)])
    out = capsys.readouterr().out
    assert "phase time" in out and "requests (2 submitted, 2 retired)" in out
    assert "other" in out and "grow" in out       # self time per phase
    assert "pool occupancy" in out


# ---------------------------------------------------------------------------
# property: every alloc event pairs with exactly one free
# ---------------------------------------------------------------------------

try:                                        # property tier rides along when
    from hypothesis import given, settings, strategies as st   # CI installs
    _OPS = st.lists(                        # it; the unit tier above must
        st.tuples(                          # still run without it
            st.sampled_from(["grow", "free", "swap_out", "swap_in",
                             "truncate"]),
            st.integers(0, 3),              # seq id
            st.integers(1, 40)),            # target token count
        min_size=1, max_size=40)
    def _property(f):
        return settings(max_examples=25, deadline=None)(
            given(ops=_OPS, num_blocks=st.integers(2, 8))(f))
except ImportError:
    def _property(f):
        def skipped():
            pytest.skip("hypothesis not installed")
        skipped.__name__ = f.__name__
        skipped.__doc__ = f.__doc__
        return skipped


@_property
def test_every_alloc_event_has_one_free_event(ops, num_blocks):
    """Replay arbitrary pool op interleavings on a *traced* pool, then audit
    the event stream alone: each block id named by an ``alloc`` instant must
    be named by exactly one later ``free`` instant (release / truncate /
    swap-out eviction), never double-freed, never freed unallocated — the
    timeline is a faithful ledger of block ownership."""
    import dataclasses as dc
    from repro.configs import get_config
    from repro.configs.base import EliteKVConfig
    from repro.core.cache import BlockManager, OutOfBlocks, PagedKVPool
    cfg = dc.replace(
        get_config("tinyllama_1_1b").reduced(num_layers=2, vocab_size=64),
        elitekv=EliteKVConfig(enabled=True, elite_r=2, d_ckv=8))
    tr = Tracer()
    pool = PagedKVPool(cfg, num_blocks=num_blocks, block_size=4, tracer=tr)
    bm = BlockManager(pool)
    swapped = {}
    for op, sid, tokens in ops:
        try:
            if op == "grow":
                bm.grow(sid, tokens)
            elif op == "free":
                bm.release(sid)
            elif op == "swap_out":
                s = bm.preempt_swap_out(sid, pool.length(sid))
                if s is not None:
                    swapped[sid] = s
            elif op == "swap_in" and sid in swapped and not pool.block_table(sid):
                bm.swap_in(sid, swapped.pop(sid))
            elif op == "truncate":
                bm.truncate(sid, min(tokens, pool.length(sid)))
        except OutOfBlocks:
            pass
    for sid in list(pool._tables):
        bm.release(sid)

    live = set()
    for ev in tr.events():
        if ev.name == "alloc":
            blocks = set(ev.arg("blocks"))
            assert not blocks & live, "block allocated while still live"
            live |= blocks
        elif ev.name == "free":
            blocks = set(ev.arg("blocks"))
            assert blocks <= live, "freed a block no alloc event granted"
            live -= blocks
    assert not live, f"alloc events without a matching free: {live}"
