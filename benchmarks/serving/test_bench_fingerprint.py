"""The dense model module reproduces, bit for bit, what the benchmark's
dense code gave before it became a module: at both configurations' widths
with 2 layers and seed 7, the SHA-256 (first 16 hex digits) of every weight
leaf, and of the reference's gaps over two fixed short requests.  The
hashes were recorded from that earlier code on the CPU; a change to a leaf,
its order, a key split or a reference block changes them."""
import hashlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from servbench import spec  # noqa: E402
from servbench.models import dense_elitekv as dense  # noqa: E402

P0 = "[0]['blocks']['p0']"
RECORDED = {
    "granite2b_ekv25_l20": {
        "gaps": "8cbc8457cca921cd",
        "leaves": {
            f"{P0}['attn']['a_kv']": "0cbcff218b64bdef",
            f"{P0}['attn']['bk']": "b1aabcc303754905",
            f"{P0}['attn']['bv']": "ffd0ff47255c2369",
            f"{P0}['attn']['wk_e']": "27793c2eb08f2950",
            f"{P0}['attn']['wo']": "509570ae3b2dbe1b",
            f"{P0}['attn']['wq']": "f1bacf77c8f4c2a0",
            f"{P0}['attn_norm']['scale']": "b771a79de7d3c062",
            f"{P0}['ffn']['w_down']": "6f5448d650f87703",
            f"{P0}['ffn']['w_gate']": "f48cfbf8a4cbd259",
            f"{P0}['ffn']['w_up']": "b20801ecb3e57db6",
            f"{P0}['ffn_norm']['scale']": "6b4d18be09cc1b3f",
            "[0]['embed']['table']": "e0fa7cf0fbb3a88b",
            "[0]['final_norm']['scale']": "f9ff93f973685104",
            "[1]['blocks']['p0']['elite_freqs']": "b1222a188cdca3ff",
        }},
    "yi6b_ekv25_l8": {
        "gaps": "41df0832f9067480",
        "leaves": {
            f"{P0}['attn']['a_kv']": "75c2ffc96e8819f7",
            f"{P0}['attn']['bk']": "56e3988944365873",
            f"{P0}['attn']['bv']": "b2ebd3e252bd7b5b",
            f"{P0}['attn']['wk_e']": "9c54687cdc241f23",
            f"{P0}['attn']['wo']": "cbc3ea7c39a276df",
            f"{P0}['attn']['wq']": "6bb3c34075d3bf8b",
            f"{P0}['attn_norm']['scale']": "27db6dbbd1c0ddff",
            f"{P0}['ffn']['w_down']": "87d5d273e0953e7e",
            f"{P0}['ffn']['w_gate']": "fc85a65830e32b01",
            f"{P0}['ffn']['w_up']": "97120d6269b5c737",
            f"{P0}['ffn_norm']['scale']": "139b20de92721b57",
            "[0]['embed']['table']": "a5c022d4b80becfb",
            "[0]['final_norm']['scale']": "898a4c226af71e5f",
            "[0]['lm_head']['w']": "1949578261380c0c",
            "[1]['blocks']['p0']['elite_freqs']": "877a10f2863abcfd",
        }},
}


def _digest(a) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("config", sorted(RECORDED))
def test_dense_module_matches_recorded_fingerprints(config):
    conf = dict(spec.config(config), num_hidden_layers=2)
    m = dense.dims(conf)
    params, buffers = dense.make(m, 7)
    leaves = {jax.tree_util.keystr(path): _digest(v) for path, v in
              jax.tree_util.tree_flatten_with_path((params, buffers))[0]}
    assert leaves == RECORDED[config]["leaves"]
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, m.vocab, n).astype(np.int32),
             [int(x) for x in rng.integers(0, m.vocab, k)])
            for n, k in ((5, 4), (11, 3))]
    gaps = dense.served_gaps(m, params, buffers, reqs, 32)
    assert _digest(np.concatenate(gaps).astype(np.float32)) == \
        RECORDED[config]["gaps"]
