"""The benchmark's files resolve, obey the contract's rules, and the runner
refuses to run off the TPU.  Needs no chip."""
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import spec, traffic, work  # noqa: E402
from servbench.models import dense_elitekv as dense  # noqa: E402

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ALLOWED_LAYERS = {"scheduler", "pool", "model step", "kernels", "device"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/serving/run.py"]
    assert BENCH["paths"] == ["benchmarks/serving"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = spec.workload(BENCH, cell)
    conf = spec.config(w["config"])
    mix = spec.traffic(w["traffic"])
    check = spec.load_json(HERE / "checks" / f"{cell}.json")
    assert check["max_logit_gap"] > 0
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert conf["num_blocks"] * conf["block_size"] >= \
        mix["scheduler"]["max_len"]
    for m in spec.metrics_for(BENCH, "per_layer", cell):
        assert callable(spec.reader(m["name"]))


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME_RE.match(n), n
    for w in BENCH["workloads"]:
        assert NAME_RE.match(w["config"]) and \
            NAME_RE.match(w["traffic"])
    for c in BENCH["configs"]:
        assert all(NAME_RE.match(k) for k in c["reduced"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["layer"] in ALLOWED_LAYERS, m


def test_every_cell_reports_what_it_must():
    for cell in CELLS:
        e2e = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", cell)}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert spec.metrics_for(BENCH, "per_layer", cell), cell
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            e2e = {x["name"] for x in
                   spec.metrics_for(BENCH, "end_to_end", cell)}
            assert m["moves"] in e2e, (m["name"], cell)


def test_configs_match_the_program_registry():
    """Each configuration keeps every published width the repo registers
    for its architecture; only the keys in ``reduced`` differ."""
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    from repro.configs import get_config
    for c in BENCH["configs"]:
        conf = spec.load_json(spec.ROOT / c["file"])
        assert c["source"] == conf["source"]
        assert c["reduced"] == conf["reduced"] == ["num_hidden_layers"]
        reg = get_config(conf["arch"])
        assert reg.d_model == conf["hidden_size"]
        assert reg.n_heads == conf["num_attention_heads"]
        assert reg.n_kv_heads == conf["num_key_value_heads"]
        assert reg.d_ff == conf["intermediate_size"]
        assert reg.vocab_size == conf["vocab_size"]
        assert reg.rope_theta == conf["rope_theta"]
        assert reg.tie_embeddings == conf["tie_word_embeddings"]
        assert conf["published"]["num_hidden_layers"] == reg.num_layers
        assert conf["num_hidden_layers"] < reg.num_layers


def _run_cpu(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/serving/run.py", "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_runner_gives_no_result_off_the_tpu():
    p = _run_cpu(spec.ROOT)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "no TPU" in p.stderr


def test_runner_fails_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    gives no result."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "serving",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cpu(tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# -- traffic ------------------------------------------------------------------

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def _first(it, n):
    return [x for _, x in zip(range(n), it)]


def _as_lists(reqs):
    return [(list(p), b) for p, b in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_traffic_deterministic_in_seed(name):
    mix = spec.traffic(name)
    big = 2 ** 33 + 5
    a, b = traffic.Mix(mix, 1000, big), traffic.Mix(mix, 1000, big)
    c = traffic.Mix(mix, 1000, 5)
    if mix["loop"] == "closed":
        ra, rb, rc = (_first(m.requests(), 40) for m in (a, b, c))
        assert _as_lists(ra) == _as_lists(rb)
        assert _as_lists(ra) != _as_lists(rc)
        assert _as_lists(a.initial()) == _as_lists(b.initial())
        # the same sizes for every seed: a whole pass is the same multiset
        sa = sorted(len(p) + q for p, q in _first(a.requests(), a.set_size))
        sc = sorted(len(p) + q for p, q in _first(c.requests(), c.set_size))
        assert sa == sc
        assert sorted(len(p) for p, _ in a.initial()) == \
            sorted(len(p) for p, _ in c.initial())
        if mix.get("order") == "fixed":   # every seed: the same sizes in turn
            assert [(len(p), q) for p, q in ra] == \
                [(len(p), q) for p, q in rc]
    else:
        ra, rb = _first(a.arrivals(), 40), _first(b.arrivals(), 40)
        assert [(t, list(p), q) for t, p, q in ra] == \
            [(t, list(p), q) for t, p, q in rb]
        n = a.set_size
        ta = _first(a.arrivals(), n)[-1][0]
        tc = _first(c.arrivals(), n)[-1][0]
        assert ta == pytest.approx(tc)


@pytest.mark.parametrize("name", MIXES)
def test_traffic_reproduces_stated_lengths(name):
    """A mix sends exactly the quantile set of each stated distribution, and
    the quantile function follows the distribution."""
    mix = spec.traffic(name)
    m = traffic.Mix(mix, 1000, 7)
    M = m.set_size
    for k, key in ((1, "prompt"), (2, "output")):
        got = sorted(size[k] for size in m.sizes)
        assert got == sorted(traffic.quantile_set(mix[key], M))
        d = mix[key]
        many = traffic.quantile_set(d, 2000)
        assert many.min() >= d["min"] and many.max() <= d["max"]
        if d["kind"] == "uniform":
            assert np.mean(many) == pytest.approx((d["min"] + d["max"]) / 2,
                                                  rel=0.01)
        else:             # the median, and sigma from the log quartiles
            assert np.median(many) == pytest.approx(d["median"], rel=0.01)
            q25, q75 = np.percentile(np.log(many), [25, 75])
            assert (q75 - q25) / 1.349 == pytest.approx(d["sigma"], rel=0.05)
    if mix["loop"] == "open":
        want = sorted(-np.log(1 - (np.arange(M) + 0.5) / M)
                      / mix["rate_per_s"])
        assert sorted(m.gaps) == pytest.approx(want)
    docs = mix.get("documents")
    if docs:
        ranks = np.asarray([d for d, _, _ in m.sizes])
        assert sorted(ranks) == sorted(traffic.zipf_ranks(
            docs["popularity"]["s"], docs["count"], M))
        w = 1 / np.arange(1, docs["count"] + 1) ** docs["popularity"]["s"]
        big = traffic.zipf_ranks(docs["popularity"]["s"], docs["count"], 5000)
        assert np.mean(big == 0) == pytest.approx(w[0] / w.sum(), abs=0.002)
        assert sorted(m.doc_lengths) == sorted(
            traffic.quantile_set(docs["length"], docs["count"]))


def test_outputs_fit_max_len():
    for name in MIXES:
        mix = spec.traffic(name)
        m = traffic.Mix(mix, 1000, 11)
        reqs = m.initial() + (
            _first(m.requests(), 200) if mix["loop"] == "closed"
            else [(p, b) for _, p, b in _first(m.arrivals(), 200)])
        for p, b in reqs:
            assert 1 <= b and len(p) + b <= mix["scheduler"]["max_len"]


# -- counting work ---------------------------------------------------------------

def _dims(name):
    return dense.dims(spec.config(name))


def test_work_counts_yi():
    m = _dims("yi6b_ekv25_l8")
    # one lane at 3000 tokens in each of the 8 layers: scores over 2r=32 +
    # 128 latent dims and a 128-wide output, 32 heads; cache rows of 4·32 +
    # 128 floats
    f, b = dense.decode_attn_work(m, [3000])
    assert f == 8 * (2 * 32 * 3000 * (32 + 128) + 2 * 32 * 3000 * 128)
    assert b == 8 * (3000 * 256 * 4 + 32 * 160 * 4 + 32 * 128 * 4)
    assert dense.decode_attn_work(m, [0, 0]) == (0.0, 0.0)
    # weights per layer: wq 4096·4096, wk_e 4096·128, a_kv 4096·128,
    # bk 128·4·96, bv 128·4·128, wo 4096·4096, mlp 3·4096·11008, 2 norms
    per_layer = (4096 * 4096 * 2 + 4096 * 128 * 2 + 128 * 4 * 96
                 + 128 * 4 * 128 + 3 * 4096 * 11008 + 2 * 4096)
    assert dense.param_count(m) == 8 * per_layer + 2 * 64000 * 4096 + 4096
    proj = 2 * (4096 * 4096 * 2 + 4096 * 128 * 2 + 3 * 4096 * 11008)
    absorb = 2 * 32 * 96 * 128 + 2 * 32 * 128 * 128
    attn = 2 * 32 * 100 * 160 + 2 * 32 * 100 * 128
    assert dense.decode_token_flops(m, 100) == \
        8 * (proj + absorb + attn) + 2 * 4096 * 64000


def test_work_counts_granite():
    m = _dims("granite2b_ekv25_l20")
    f, b = dense.decode_attn_work(m, [10, 20])
    assert f == 20 * 30 * (2 * 32 * (16 + 128) + 2 * 32 * 128)
    assert b == 20 * (30 * (8 * 16 + 128) * 4
                      + 2 * (32 * 144 + 32 * 128) * 4)
    up = 2 * 128 * 8 * 48 + 2 * 128 * 8 * 64
    proj = 2 * (2048 * 2048 * 2 + 2048 * 128 * 2 + 3 * 2048 * 8192)
    assert dense.prefill_token_flops(m, 9) == \
        20 * (proj + up + 4 * 32 * 64 * 10)
    total = sum(dense.prefill_token_flops(m, p) for p in range(300, 556))
    assert dense.prefill_flops(m, 300, 256) == pytest.approx(total, rel=1e-12)
    assert dense.head_flops(m) == 2 * 2048 * 49155
    peaks = spec.peaks("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert work.roofline_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)


def test_peaks_refuse_unknown_device():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
