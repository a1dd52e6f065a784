"""Compile the Pallas kernels and one full-width decode step for a TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib compiles
for a *described* v5e topology, which refuses what the chip's compiler would
refuse — block shapes that break the (8, 128) tiling, programs that do not
fit the device.  Interpret mode cannot see either.  Shapes are the widths
``launch/serve.py`` serves ``tinyllama_1_1b`` at with ``--elitekv`` (25%
cache): elite_r 8 (2r = 16), d_ckv 64, 32 query heads over 4 kv heads,
block size 16, 8 lanes.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, so the description
happens only in the worker that runs this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.convert import pick_dims
from repro.kernels import elite_decode as ed
from repro.kernels import flash_prefill as fp
from repro.kernels import rope_elite as re_k

B, NH, NKV, R2, DC, BS, W = 8, 32, 4, 16, 64, 16, 3
G = NH // NKV
N_BLOCKS, MB = 1024, 32
HBM_BYTES = 16 * 2**30                      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


#: lanes, query heads, kv heads, 2r, d_c, table entries, pool blocks
PAGED_WIDTHS = {
    "tinyllama": (B, NH, NKV, R2, DC, MB, N_BLOCKS),  # streams under 128 lanes
    "yi6b_docqa": (64, 32, 4, 32, 128, 256, 16384),
    "granite2b_chat": (128, 32, 8, 16, 128, 128, 10240),
}


def _paged_args(one_chip, kernel, widths):
    b, nh, nkv, r2, dc, mb, n_blocks = widths
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    n_slots = n_blocks * BS
    q8 = kernel.endswith("_q8")
    pdt = jnp.int8 if q8 else jnp.float32
    pages = [s((n_slots, nkv * r2), pdt), s((n_slots, dc), pdt),
             s((n_slots, dc), pdt)]
    pages += [s((n_slots,))] * 3 if q8 else []
    if kernel.startswith("elite_verify"):
        q = [s((b, W, nh, r2)), s((b, W, nh, dc))]
    else:
        q = [s((b, nh, r2)), s((b, nh, dc))]
    if kernel.startswith("elite_decode_sparse"):
        walk = [s((b, 8), jnp.int32), s((b, 8), jnp.int32)]
    elif kernel.startswith("elite_verify"):
        walk = [s((b, mb), jnp.int32), s((b,), jnp.int32), s((b,), jnp.int32)]
    else:
        walk = [s((b, mb), jnp.int32), s((b,), jnp.int32)]
    return q + pages + walk


PAGED_KERNELS = [
    "elite_decode_paged", "elite_decode_paged_q8",
    "elite_decode_sparse_paged", "elite_decode_sparse_paged_q8",
    "elite_verify_paged", "elite_verify_paged_q8",
]


def _compile_paged(one_chip, kernel, widths):
    fn = getattr(ed, kernel)
    nh, nkv = PAGED_WIDTHS[widths][1:3]
    step = jax.jit(lambda *a: fn(*a, q_group=nh // nkv, scale=0.125,
                                 block_size=BS))
    compiled = step.lower(*_paged_args(one_chip, kernel,
                                       PAGED_WIDTHS[widths])).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", PAGED_KERNELS)
def test_paged_kernel_compiles_for_v5e(one_chip, kernel):
    _compile_paged(one_chip, kernel, "tinyllama")


@pytest.mark.parametrize("widths", ["yi6b_docqa", "granite2b_chat"])
@pytest.mark.parametrize("kernel", PAGED_KERNELS)
def test_paged_kernel_compiles_at_cell_widths(one_chip, kernel, widths):
    """The benchmark cells' widths: docqa's 64 lanes over 256 table
    entries, chat's 128 lanes over 128 (separate latent streams; the
    joint-latent decode is the next test)."""
    _compile_paged(one_chip, kernel, widths)


@pytest.mark.parametrize("widths", ["yi6b_docqa", "granite2b_chat"])
def test_joint_latent_decode_compiles_for_v5e(one_chip, widths):
    """The serving path's call: one joint ``c`` stream (``c_v_pages=None``),
    fetched once per page for both roles."""
    nh, nkv = PAGED_WIDTHS[widths][1:3]
    q_e, q_lat, k_e, c, _, bt, ln = _paged_args(
        one_chip, "elite_decode_paged", PAGED_WIDTHS[widths])
    step = jax.jit(lambda q_e, q_lat, k_e, c, bt, ln: ed.elite_decode_paged(
        q_e, q_lat, k_e, c, None, bt, ln, q_group=nh // nkv, scale=0.125,
        block_size=BS))
    compiled = step.lower(q_e, q_lat, k_e, c, bt, ln).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_contiguous_decode_compiles_for_v5e(one_chip):
    S = 512
    args = [_spec(one_chip, (B, NH, R2)), _spec(one_chip, (B, NH, DC)),
            _spec(one_chip, (B, S, NKV, R2)), _spec(one_chip, (B, S, DC)),
            _spec(one_chip, (B, S, DC)), _spec(one_chip, (B,), jnp.int32)]
    step = jax.jit(lambda *a: ed.elite_decode(*a, q_group=G, scale=0.125,
                                              block_s=128))
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()


def test_flash_prefill_compiles_for_v5e(one_chip):
    S, dh = 512, 64
    args = [_spec(one_chip, (2, S, NH, dh)), _spec(one_chip, (2, S, NKV, dh)),
            _spec(one_chip, (2, S, NKV, dh))]
    step = jax.jit(lambda q, k, v: fp.flash_prefill(
        q, k, v, G, dh ** -0.5, block_q=128, block_k=128, q_offset=0))
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()


def test_rope_elite_compiles_for_v5e(one_chip):
    S = 512
    args = [_spec(one_chip, (2, S, NH, R2)), _spec(one_chip, (S,), jnp.int32),
            _spec(one_chip, (NH, R2 // 2))]
    step = jax.jit(lambda x, p, f: re_k.rope_elite(x, p, f, block_s=128))
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()


def _compile_decode_step(one_chip, cfg):
    """The serving decode step of ``cfg`` over a 1024×16-slot pool, compiled
    for one described chip: ``(compiled, pages)``."""
    from repro.core.cache import PagedKVPool
    from repro.models import lm

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params, buffers = on_chip(jax.eval_shape(
        lambda key: lm.init(key, cfg), jax.random.PRNGKey(0)))
    pages = on_chip(jax.eval_shape(
        lambda: PagedKVPool(cfg, N_BLOCKS, BS).pages))
    i32 = lambda shape: _spec(one_chip, shape, jnp.int32)

    def decode(params, buffers, tokens, pages, slot_mapping, block_tables,
               lengths):
        return lm.apply_decode_paged(params, buffers, cfg, {"tokens": tokens},
                                     pages, slot_mapping, block_tables,
                                     lengths, block_size=BS)

    compiled = jax.jit(decode, donate_argnums=(3,)).lower(
        params, buffers, i32((B, 1)), pages, i32((B,)), i32((B, MB)),
        i32((B,))).compile()
    return compiled, pages


def test_full_width_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The serving decode step of full-width tinyllama_1_1b (22 layers, vocab
    32000, f32) with a 1024×16-slot pool: the paged Pallas kernel is in the
    program, and arguments + outputs + temporaries fit one chip's HBM."""
    from repro.kernels import ops

    base = get_config("tinyllama_1_1b")
    cfg = dataclasses.replace(base, elitekv=pick_dims(base, 0.25, align=16))
    assert 2 * cfg.elitekv.elite_r == R2 and cfg.elitekv.d_ckv == DC
    # the kernel dispatch asks the platform, and the platform here is the
    # CPU: steer it to the Pallas path the chip takes
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    compiled, _ = _compile_decode_step(one_chip, cfg)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_decode_step_keeps_128_wide_pool_layout(one_chip, monkeypatch):
    """Where the pool's streams are 128 wide (Yi-6B's EliteKV widths: 4 kv
    heads × 2r 32, d_ckv 128), no layer of the decode scan relays out its
    pool slice: every copy over the slot axis moves a whole
    [n_super, n_slots, 128] stack (the scan's carry), never a per-layer
    [1, n_slots, ...] slice, so the step costs no pool-sized copy per layer."""
    import re
    from repro.configs.base import EliteKVConfig
    from repro.kernels import ops

    cfg = dataclasses.replace(
        get_config("tinyllama_1_1b"), num_layers=4,
        elitekv=EliteKVConfig(enabled=True, elite_r=16, d_ckv=128))
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    compiled, pages = _compile_decode_step(one_chip, cfg)
    assert {a.shape for a in pages["p0"].values()} == {(4, N_BLOCKS * BS, 128)}
    copies = [shape.split(",") for shape in re.findall(
        r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", compiled.as_text())]
    pool = [c for c in copies if str(N_BLOCKS * BS) in c]
    assert pool and all(c[0] == "4" for c in pool), pool
