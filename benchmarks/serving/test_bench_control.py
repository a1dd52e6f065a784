"""The correctness check can fail.  At a small size on the CPU the control
(the reference with float8 matmul operands) reads past the limit that sound
runs stay under, and a whole run of the harness (its look for a chip
skipped) comes out not correct when the served path is broken underneath:
a token altered where it is produced, or a decode step that leaves the pool
unchanged."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import harness, spec  # noqa: E402

CONF = dict(model="dense_elitekv",
            arch="tiny", hidden_size=128, intermediate_size=256,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=2, vocab_size=512, rope_theta=10000.0,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            elitekv=dict(elite_r=4, d_ckv=32, lrd="joint"),
            dtype="float32", pool_dtype="float32", block_size=16,
            num_blocks=128)
LIMIT = 1e-4          # sound CPU runs read 0: the program is exact float32


def _cell():
    mix = spec.traffic("docqa")
    mix.update(clients=4, set_size=64,
               prompt={"kind": "uniform", "min": 8, "max": 40},
               output={"kind": "uniform", "min": 8, "max": 32})
    mix["documents"].update(count=3, length={"kind": "uniform", "min": 40,
                                             "max": 90})
    mix["scheduler"].update(max_slots=4, max_len=256,
                            prefill_chunk_tokens=32, prefill_batch_lanes=2)
    bench = spec.benchmark()
    return harness.Cell(name="tiny", chips=1, conf=CONF, mix=mix,
                        check={"max_logit_gap": LIMIT, "min_tokens": 128,
                               "max_requests": 6},
                        e2e=spec.metrics_for(bench, "end_to_end",
                                             "yi6b.docqa"),
                        per_layer=[])


@pytest.fixture
def no_chip_check(monkeypatch):
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})


def _run(seed=21):
    return harness.run_cell(_cell(), seed, 1.5, False, time.perf_counter())


def test_sound_run_is_correct(no_chip_check):
    res = _run()
    assert res["correct"] is True
    assert res["check"]["max_logit_gap"]["value"] <= LIMIT
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p90_ms",
                                   "itl_p95_ms", "setup_s"}


def test_altered_token_is_caught(no_chip_check, monkeypatch):
    from repro.runtime import serve_loop
    finish = serve_loop.Scheduler._maybe_finish

    def altered(self, slot, token):
        req = self.slots[slot]
        if len(req.generated) == 3:          # the third token of each request
            req.generated[-1] = (req.generated[-1] + 1) % CONF["vocab_size"]
        return finish(self, slot, req.generated[-1])

    monkeypatch.setattr(serve_loop.Scheduler, "_maybe_finish", altered)
    assert _run()["correct"] is False


def test_unchanged_pool_is_caught(no_chip_check, monkeypatch):
    from repro.runtime import serve_loop
    init = serve_loop.Scheduler.__init__

    def stale_decode(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode

        def keep_pages(params, buffers, tokens, pages, *rest):
            logits, _ = decode(params, buffers, tokens, pages, *rest)
            return logits, pages             # the step's writes are lost

        self._decode = keep_pages

    monkeypatch.setattr(serve_loop.Scheduler, "__init__", stale_decode)
    assert _run()["correct"] is False


def test_fp8_control_fails_the_limit():
    """The control (the reference with float8 matmul operands) puts first,
    at some rows of served requests, a token the float32 reference ranks
    well below its best: the run's own comparison finds it not correct."""
    cell = _cell()
    m = cell.dims
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, m.vocab, n).astype(np.int32),
             list(rng.integers(0, m.vocab, 60))) for n in (30, 90, 150)]
    chk = harness.check_served(cell, 5, reqs, control=True)
    assert chk["max_logit_gap"] > 3 * LIMIT
    assert harness.is_correct(chk, LIMIT) is False


def test_control_run_comes_out_not_correct(no_chip_check):
    """``control.py`` on a served window: the program's reading is correct,
    the control's, through the same comparison and limit, is not."""
    import control
    row = control.readings(_cell(), 21, 1.5, time.perf_counter(),
                           harness.CompileLog())
    assert row["limit"] == LIMIT and row["attempted"] > 0
    assert row["program"]["correct"] is True
    assert row["control"]["correct"] is False
    assert row["control"]["tokens"] == row["program"]["tokens"] > 0
