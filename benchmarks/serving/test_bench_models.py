"""Every model module under ``servbench/models/`` keeps the contract the
harness relies on, at its tiny size on the CPU; and the harness's counters
read what the scheduler counted."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import harness, spec, traffic  # noqa: E402

MODULES = sorted(p.stem for p in (HERE / "servbench" / "models").glob("*.py")
                 if p.stem != "__init__")
MIX = {"loop": "closed", "clients": 3,
       "prompt": {"kind": "uniform", "min": 8, "max": 40},
       "output": {"kind": "uniform", "min": 4, "max": 12},
       "steady_start": False, "set_size": 32,
       "scheduler": {"max_slots": 4, "max_len": 128,
                     "prefill_chunk_tokens": 16, "prefill_batch_lanes": 2,
                     "prefix_cache": True, "eviction": "recompute"}}
LIMIT = 1e-4          # sound CPU runs read 0: the program is exact float32


def _cell(module: str, mix=MIX) -> harness.Cell:
    mod = spec.model(module)
    return harness.Cell(name="tiny", chips=1, conf=dict(mod.TINY), mix=mix,
                        check={"max_logit_gap": LIMIT, "min_tokens": 64,
                               "max_requests": 4},
                        e2e=spec.metrics_for(spec.benchmark(), "end_to_end",
                                             "granite2b.chat"),
                        per_layer=[])


def test_the_dense_module_is_found():
    assert "dense_elitekv" in MODULES
    with pytest.raises(ValueError):
        spec.model("../weights")


@pytest.mark.parametrize("module", MODULES)
def test_module_serves_and_checks_through_the_harness(module, monkeypatch):
    """Loading by name, seeded weights, the program's paged prefill and
    decode over them, and the module's reference judging what was served:
    a whole run of the harness (its look for a chip skipped) is correct."""
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    cell = _cell(module)
    assert cell.conf["model"] == module
    hash(cell.dims)
    assert cell.dims.vocab > 0 and cell.dims.layers > 0
    res = harness.run_cell(cell, 2 ** 33 + 1, 1.0, False, time.perf_counter())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is True
    assert 0.0 <= res["check"]["max_logit_gap"]["value"] <= LIMIT


@pytest.mark.parametrize("module", MODULES)
def test_module_control_and_weights(module):
    """The same seed gives the same weights; the control reading covers the
    same rows as the judge's."""
    import jax
    cell = _cell(module)
    mod, m = cell.model, cell.dims
    a = jax.tree_util.tree_leaves(mod.make(m, 5))
    b = jax.tree_util.tree_leaves(mod.make(m, 5))
    c = jax.tree_util.tree_leaves(mod.make(m, 6))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, m.vocab, n).astype(np.int32),
             list(rng.integers(0, m.vocab, 9))) for n in (7, 30)]
    judge = harness.check_served(cell, 5, reqs)
    control = harness.check_served(cell, 5, reqs, control=True)
    assert judge["tokens"] == control["tokens"] == 18
    assert judge["max_logit_gap"] >= 0 and control["max_logit_gap"] >= 0


@pytest.mark.parametrize("module", MODULES)
def test_module_work_counts(module):
    mod = spec.model(module)
    m = mod.dims(mod.TINY)
    assert mod.decode_attn_work(m, []) == (0.0, 0.0)
    f1, b1 = mod.decode_attn_work(m, [10])
    f2, b2 = mod.decode_attn_work(m, [10, 30])
    assert 0 < f1 < f2 and 0 < b1 < b2
    assert mod.head_flops(m) > 0
    assert mod.decode_token_flops(m, 20) > mod.decode_token_flops(m, 10) \
        > mod.head_flops(m)
    assert mod.prefill_flops(m, 0, 0) == 0
    assert mod.prefill_flops(m, 16, 16) > mod.prefill_flops(m, 0, 16) > 0


# -- the scheduler's counters -------------------------------------------------

def test_counters_read_the_scheduler():
    """Served to completion, the window's counters are the scheduler's own
    (prefix cache, prefill calls and their capacity, steps and their wall
    time), and the second phase's requests, which extend the first's, hit
    the prefix cache."""
    cell = _cell("dense_elitekv")
    m = cell.dims
    rng = np.random.default_rng(4)
    docs = [rng.integers(0, m.vocab, n).astype(np.int32) for n in (40, 56)]
    phases = [[(d, 5) for d in docs],
              [(np.concatenate([d, rng.integers(0, m.vocab, 9).astype(
                  np.int32)]), 7) for d in docs]]
    params, buffers = cell.model.make(m, 3)
    sched = harness.build_scheduler(cell, params, buffers)
    server = harness.Server(cell, sched, traffic.Mix(cell.mix, m.vocab, 1))
    w = server.window = harness.Window()
    w.counters0 = server.counters()
    off = harness._annotator(False)
    for batch in phases:
        recs = [server.send(p, b, -1, harness.clock()) for p, b in batch]
        server.drain(off, lambda: all(r.done is not None for r in recs))
    c = w.counters1 = server.counters()
    server.window = None

    assert len(sched.finished) == 4
    assert c["hit_tokens"] == sched.bm.prefix.hit_tokens > 0
    assert c["lookup_tokens"] == sched.bm.prefix.lookup_tokens
    assert c["prefill_calls"] == sched.prefill_chunks > 0
    assert c["decode_steps"] == sched.report(1.0).decode_steps
    assert w.delta("steps") == w.steps > 0
    assert 0 < w.delta("step_wall_ms") <= 1e3 * w.step_wall_s
    assert w.delta("prefill_tokens") <= w.delta("prefill_capacity_tokens")
    assert 0 < w.delta("prefill_capacity_tokens") <= \
        w.delta("prefill_calls") * 2 * 16      # lanes × chunk at most
