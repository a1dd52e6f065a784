"""Subprocess worker for multi-device serving checks.

The conftest pins the test process to ONE CPU device (determinism), so every
multi-device check — tests/test_sharded_serving.py, the benchmark scaling
rows, and the CI ``sharded`` job — runs this module in a fresh subprocess
that forces its own host-device count *before* importing jax:

    PYTHONPATH=src python -m repro.runtime.sharded_check \
        --devices 8 --tp 2 --dp 2 --scenarios plain,recompute,prefix,int8,spec

It serves a fixed deterministic request set (greedy, seeded) through each
scenario on a tiny 2-layer EliteKV model and prints ONE JSON object on
stdout: per-scenario ``{uid: tokens}`` streams plus report fields (tok/s,
ttft percentiles, per-replica occupancy, pool bytes per device).  The caller
compares token streams across (tp, dp) settings — the sharded serving path
(kernels/ops.py TP wrappers + runtime/router.py) is bit-identical to
single-device, so ``tokens`` must match EXACTLY, not approximately.

``--parity`` instead checks the shard_map decode/verify epilogue directly
against the single-device kernels on random operands (bitwise equality),
covering f32 and int8 pages at every tp that divides the head count.

Scenario knobs mirror launch/serve.py flags: ``plain`` (chunked prefill +
swap eviction under pool pressure), ``recompute`` (same, recompute
eviction), ``prefix`` (content-addressed prefix cache + shared prompt
prefix), ``int8`` (quantized pool), ``spec`` (self-speculative decode).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# SchedulerConfig overrides per scenario; "shared" is the shared-prompt-prefix
# length (request-builder knob, not a SchedulerConfig field).
SCENARIOS = {
    "plain": dict(eviction="swap"),
    "recompute": dict(eviction="recompute"),
    "prefix": dict(prefix_cache=True, shared=16),
    "int8": dict(cache_dtype="int8"),
    "spec": dict(speculate_k=2),
}
N_REQUESTS = 6
NEW_TOKENS = 8


def _build_requests(serve_loop, prompts, shared: int = 0):
    """Fresh Request objects every call — ``generated`` is mutable, so
    reusing requests across runs would leak one run's tokens into the next."""
    pre = list(range(1, 1 + shared))
    return [serve_loop.Request(uid=i, prompt=pre + prompts[i],
                               max_new_tokens=NEW_TOKENS, arrival=i // 2,
                               temperature=0.0, top_p=1.0, seed=100 + i)
            for i in range(N_REQUESTS)]


def _run_scenario(name, params, buffers, cfg, tp, dp, prompts):
    import jax
    from repro.launch.mesh import make_serving_mesh, replica_meshes
    from repro.runtime import serve_loop
    from repro.runtime.router import Router

    kw = dict(SCENARIOS[name])
    shared = kw.pop("shared", 0)
    scfg = serve_loop.SchedulerConfig(
        max_slots=2, block_size=8, num_blocks=24, prefill_chunk_tokens=8,
        max_new_tokens=NEW_TOKENS, **kw)
    reqs = _build_requests(serve_loop, prompts, shared=shared)
    meshes = None
    if tp > 1 or dp > 1:
        meshes = replica_meshes(make_serving_mesh(tp=tp, dp=dp))
    if dp > 1:
        router = Router(params, buffers, cfg, scfg, num_replicas=dp,
                        meshes=meshes)
        rep = router.run(reqs)
        pool0 = router.replicas[0].pool
        return {
            "tokens": {str(u): t for u, t in router.finished_tokens().items()},
            "report": {
                "completed": rep.completed,
                "tok_s": rep.tok_per_s,
                "ttft_wall_p50_ms": rep.ttft_wall_p50_ms,
                "ttft_wall_p95_ms": rep.ttft_wall_p95_ms,
                "preemptions": rep.preemptions,
                "routed": rep.routed,
                "imbalance": rep.imbalance,
                "occupancy_per_replica": [r.mean_occupancy for r in rep.replicas],
                "pool_bytes_per_token_per_device": pool0.bytes_per_token_per_device(),
            },
        }
    mesh = meshes[0] if meshes else None
    sched = serve_loop.Scheduler(params, buffers, cfg, scfg, mesh=mesh)
    rep = sched.run(reqs)
    return {
        "tokens": {str(r.uid): list(r.generated) for r in sched.finished},
        "report": {
            "completed": rep.completed,
            "tok_s": rep.tok_per_s,
            "ttft_wall_p50_ms": rep.ttft_wall_p50_ms,
            "ttft_wall_p95_ms": rep.ttft_wall_p95_ms,
            "preemptions": rep.preemptions,
            "routed": [len(sched.finished)],
            "imbalance": 1.0,
            "occupancy_per_replica": [rep.mean_occupancy],
            "pool_bytes_per_token_per_device": sched.pool.bytes_per_token_per_device(),
        },
    }


def _run_parity():
    """Bitwise kernel-vs-oracle parity for the shard_map TP epilogue."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_serving_mesh

    rng = np.random.default_rng(0)
    B, nh, nkv, r2, d_c, bs, nb = 3, 4, 4, 8, 4, 8, 6
    G = nh // nkv
    n_slots = nb * bs
    q_e = jnp.asarray(rng.standard_normal((B, nh, r2)), jnp.float32)
    q_lat = jnp.asarray(rng.standard_normal((B, nh, d_c)), jnp.float32)
    K = jnp.asarray(rng.standard_normal((n_slots, nkv * r2)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((n_slots, d_c)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, 4)), jnp.int32)
    ln = jnp.asarray([5, 17, 30], jnp.int32)
    out = {}

    ref = kops.elite_decode_paged(q_e, q_lat, K, C, C, bt, ln, G, 0.5, bs,
                                  force_xla=True)
    for tp in (2, 4):
        got = kops.elite_decode_paged_tp(
            q_e, q_lat, K, C, C, None, bt, ln, G, 0.5, bs,
            mesh=make_serving_mesh(tp=tp), force_xla=True)
        out[f"decode_tp{tp}"] = bool(jnp.all(got == ref))

    W = 3
    qv_e = jnp.asarray(rng.standard_normal((B, W, nh, r2)), jnp.float32)
    qv_lat = jnp.asarray(rng.standard_normal((B, W, nh, d_c)), jnp.float32)
    qo = jnp.asarray([2, 10, 20], jnp.int32)
    refv = kops.elite_verify_paged(qv_e, qv_lat, K, C, C, bt, qo, ln, G, 0.5,
                                   bs, force_xla=True)
    gotv = kops.elite_verify_paged_tp(
        qv_e, qv_lat, K, C, C, None, bt, qo, ln, G, 0.5, bs,
        mesh=make_serving_mesh(tp=2), force_xla=True)
    out["verify_tp2"] = bool(jnp.all(gotv == refv))

    Kq = jnp.asarray(rng.integers(-127, 127, (n_slots, nkv * r2)), jnp.int8)
    Cq = jnp.asarray(rng.integers(-127, 127, (n_slots, d_c)), jnp.int8)
    ks = jnp.asarray(rng.random((n_slots,)) + 0.1, jnp.float32)
    cs = jnp.asarray(rng.random((n_slots,)) + 0.1, jnp.float32)
    refq = kops.elite_decode_paged_q8(q_e, q_lat, Kq, Cq, Cq, ks, cs, cs, bt,
                                      ln, G, 0.5, bs, force_xla=True)
    gotq = kops.elite_decode_paged_tp(
        q_e, q_lat, Kq, Cq, Cq, (ks, cs, cs), bt, ln, G, 0.5, bs,
        mesh=make_serving_mesh(tp=2), force_xla=True)
    out["decode_q8_tp2"] = bool(jnp.all(gotq == refq))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host device count (set before jax import)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--scenarios", default="plain",
                    help=f"comma list from {sorted(SCENARIOS)}")
    ap.add_argument("--parity", action="store_true",
                    help="run shard_map kernel-vs-oracle bitwise parity "
                         "instead of serving scenarios")
    args = ap.parse_args(argv)

    # must land before jax initialises; harmless if the parent already set it
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import dataclasses
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.configs.base import EliteKVConfig
    from repro.models import lm

    result = {"devices": jax.device_count(), "tp": args.tp, "dp": args.dp}
    if args.parity:
        result["parity"] = _run_parity()
        json.dump(result, sys.stdout)
        return result

    cfg = dataclasses.replace(
        get_config("tinyllama_1_1b").reduced(num_layers=2, vocab_size=128),
        elitekv=EliteKVConfig(enabled=True, elite_r=4, d_ckv=64))
    params, buffers = lm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(1, 128, 12 + i)))
               for i in range(N_REQUESTS)]
    result["scenarios"] = {
        name: _run_scenario(name, params, buffers, cfg, args.tp, args.dp,
                            prompts)
        for name in args.scenarios.split(",")}
    json.dump(result, sys.stdout)
    return result


if __name__ == "__main__":
    main()
