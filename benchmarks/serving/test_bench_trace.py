"""Trace reduction: busy and idle time, kernel time, idle gaps by host span,
and the readers that divide by the cell's chips; on hand-made timelines and
on a small trace recorded on a TPU v5e (``testdata/small.xplane.pb``,
written by ``record_testdata.py``)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import harness, readers, xtrace  # noqa: E402
from servbench.models import dense_elitekv as dense  # noqa: E402
from servbench.xtrace import Op, Reduced  # noqa: E402

SMALL = HERE / "testdata" / "small.xplane.pb"


def _reduced():
    # window [0, 10]; core 0 busy [1,3] ∪ [2,4] ∪ [6,7]; core 1 busy [0,10]
    core0 = [Op("fusion.1", 1, 3), Op("elite_decode_paged", 2, 4),
             Op("fusion.2", 6, 7)]
    core1 = [Op("elite_decode_paged", 0, 10)]
    host = [("bench.step", 0, 5), ("PjitFunction(_decode)", 0.5, 1.5),
            ("bench.record", 5, 6), ("bench.step", 6, 10)]
    return Reduced(window=(0.0, 10.0), ops=[core0, core1], host=host)


def test_busy_is_the_union_averaged_over_cores():
    r = _reduced()
    assert r.window_s == 10.0
    assert r.busy_s() == pytest.approx((4.0 + 10.0) / 2)
    assert r.idle_share() == pytest.approx(0.3)


def test_kernel_time_by_name():
    r = _reduced()
    assert r.op_seconds("elite_decode_paged") == pytest.approx((2 + 10) / 2)
    assert r.op_seconds("fusion") == pytest.approx(3 / 2)
    assert r.top_ops(1) == [["elite_decode_paged", 6.0]]


def test_idle_gaps_named_by_innermost_host_span():
    r = _reduced()
    assert r.idle_gaps() == [(0.0, 1), (4, 6), (7, 10.0)]
    # gap [0,1] centres on 0.5: inside the dispatch span within bench.step
    assert r.host_at(0.5) == "PjitFunction(_decode)"
    tot = dict(map(tuple, r.idle_by_host()))
    assert tot == pytest.approx({"PjitFunction(_decode)": 1.0,  # [0,1]
                                 "bench.record": 2.0,           # [4,6]
                                 "bench.step": 3.0})            # [7,10]
    assert r.idle_by_host(1) == [["bench.step", 3.0]]


def test_shares_of_a_peak_divide_by_the_chips():
    """MFU and the kernel's roofline share of a cell on N chips are over N
    chips' peaks: the same work and trace read 1/N of one chip's share."""
    m = dense.dims(dense.TINY)
    traced = harness.Window(model_flops=2e12, decode_lengths=[[100, 200]])
    peaks = {"flops_per_s": 1e14, "bytes_per_s": 1e11}

    def ctx(chips):
        return harness.Ctx(dims=m, peaks=peaks, window=harness.Window(),
                           traced=traced, trace=_reduced(), model=dense,
                           chips=chips)

    assert readers.mfu(ctx(1)) == pytest.approx(100 * 2e12 / (10 * 1e14))
    assert readers.mfu(ctx(2)) == pytest.approx(readers.mfu(ctx(1)) / 2)
    f, b = dense.decode_attn_work(m, [100, 200])
    least = max(f / 1e14, b / 1e11)
    assert readers.decode_attn_roofline(ctx(1)) == pytest.approx(
        100 * least / 6.0)                   # the kernel's mean of 2 + 10 s
    assert readers.decode_attn_roofline(ctx(2)) == pytest.approx(
        100 * least / 12.0)                  # its sum over both chips


def test_prefill_fill_reads_the_counters():
    w = harness.Window(counters0={"prefill_tokens": 100.0,
                                  "prefill_capacity_tokens": 2048.0},
                       counters1={"prefill_tokens": 612.0,
                                  "prefill_capacity_tokens": 6144.0})
    ctx = harness.Ctx(dims=None, peaks={}, window=w)
    assert readers.prefill_fill(ctx) == pytest.approx(12.5)
    assert readers.prefill_fill(harness.Ctx(
        dims=None, peaks={}, window=harness.Window())) is None


def test_no_device_ops_is_all_idle():
    r = Reduced(window=(0.0, 2.0), ops=[], host=[])
    assert r.busy_s() == 0.0 and r.idle_share() == 1.0
    assert r.idle_by_host() == [["no host span", 2.0]]


def test_merge_and_union():
    assert xtrace._merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xtrace._union([(0, 1), (1, 2), (5, 6)]) == pytest.approx(3.0)


# -- the recorded trace ------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    return xtrace.reduce(str(SMALL))


def test_recorded_window_and_busy_time(small):
    assert small.window_s == pytest.approx(0.310887127, abs=1e-9)
    assert len(small.ops) == 1                      # one TensorCore
    assert small.busy_s() == pytest.approx(0.011773584, abs=1e-9)
    assert 0.96 < small.idle_share() < 0.97


def test_recorded_kernel_time(small):
    assert small.op_seconds("elite_decode_paged") == pytest.approx(
        0.003445476, abs=1e-9)
    top = small.top_ops(3)
    assert top[0][0] == "elite_decode_paged f32[8,8,128]"
    assert top[0][1] <= small.op_seconds("elite_decode_paged")


def test_recorded_idle_gaps_by_host(small):
    named = dict(map(tuple, small.idle_by_host(20)))
    assert sum(named.values()) == pytest.approx(
        small.window_s - small.busy_s(), rel=1e-6)
    assert named["bench.step"] > 0.1               # host work inside a step
    assert "PjitFunction(_argmax)" in named        # the decode step's argmax
