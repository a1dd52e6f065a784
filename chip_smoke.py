"""One-process smoke test of the EliteKV serving stack on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded path only

On one chip, in order:

  (a) device check: exit non-zero, printing no result, unless JAX's first
      device is a TPU;
  (b) parity of the six paged Pallas kernels with their ``kernels/ref.py``
      oracles, at the widths ``launch/serve.py`` serves tinyllama_1_1b with
      ``--elitekv`` (2r = 16, d_ckv = 64, 32 heads over 4 kv heads, block 16),
      8 lanes of a few hundred tokens;
  (c) serving leg one through ``launch/serve.py``: full-width tinyllama_1_1b
      (22 layers, d_model 2048, vocab 32000, f32, random weights from
      ``--seed``), an f32 pool, chunked prefill, the prefix cache over a
      shared prompt prefix, and host-swap eviction in a pool small enough to
      preempt;
  (d) serving leg two: an int8 pool with speculative decode (k = 2);
  (e) in each leg the compiled decode (and verify) step holds the Pallas
      kernel (``tpu_custom_call``), and one decode step over the leg's pages
      gives the same logits with the kernel and with the XLA gather path.

``--four-chips`` runs only the sharded path: the same greedy requests at
tp=4 and at tp=2 x dp=2, against one chip in the same process, asserting
identical tokens.

Every phase that fails raises, so the script exits non-zero.  Lines before
the last are observations, not metrics; the last line is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter, defaultdict

import jax

# -- tolerances ---------------------------------------------------------------
# (b) The oracles run at "highest" (true f32) matmul precision; the kernels'
# f32 dots run at the TPU compiler's default MXU precision, which may round
# each operand to bf16 (2^-9 relative).  Scores are ~80-term dots scaled by
# 1/sqrt(64), so they move by up to ~1e-2 and the outputs, softmax means of
# N(0, 1) latent rows, by a few 1e-3.  1e-2 covers that; a wrong page, kv
# head or mask changes outputs by their own scale (~0.1-1).
KERNEL_TOL = 1e-2
# (e) Both decode steps run every projection at XLA's default TPU precision
# (bf16 MXU passes for f32); only the attention core differs, by the
# rounding above (~2^-8 of the attention output), compounded through 22
# residual layers.  Over an int8 pool the two cores round differently too:
# the kernel feeds the MXU exact int8 values and scales the scores, the XLA
# path rounds the dequantized f32 rows.  5% of the logits' scale covers
# both; attending the wrong pages moves logits by their whole scale.
LOGIT_TOL = 5e-2

# widths launch/serve.py picks for full-width tinyllama_1_1b at a 25% cache
NH, NKV, R2, DC, BS = 32, 4, 16, 64, 16
FULL_WIDTH = (22, 2048, 32000)          # layers, d_model, vocab
LANES = 8

# the two serving legs: launch/serve.py flags
MODEL = ["--arch", "tinyllama_1_1b", "--elitekv", "--stream"]
TRAFFIC = ["--requests", "8", "--rate", "0.5", "--max-slots", "8",
           "--prompt-len", "256", "--shared-prefix", "128",
           "--new-tokens", "32", "--block-size", str(BS),
           "--prefill-chunk", "128"]
LEG_ONE = ["--num-blocks", "64", "--prefix-cache", "--eviction", "swap"]
LEG_TWO = ["--num-blocks", "192", "--pool-dtype", "int8", "--speculate", "2"]


class CompileLog:
    """Seconds spent tracing, lowering and compiling each jitted function,
    and persistent-cache events, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = defaultdict(float)
        self.events = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            name = kw.get("fun_name", "?")
            self.seconds[name.removeprefix("jit(").removesuffix(")")] += duration

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def report(self, names):
        """Print the seconds of ``names`` since the last report."""
        for n in names:
            observe(f"compile_s[{n}]", self.seconds.get(n, 0.0))
        self.seconds.clear()


def observe(name, value):
    print(f"observation: {name} = {value}", flush=True)


# -- (b) kernel parity -------------------------------------------------------

def kernel_cases(seed: int):
    """Random operands for the six paged kernels at the serving widths:
    → ({kernel name: positional args}, keyword args)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import quant
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    G, scale, mb, n_blocks, W = NH // NKV, 0.125, 32, 512, 3
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q_e, q_lat = f32(LANES, NH, R2), f32(LANES, NH, DC)
    qv_e, qv_lat = f32(LANES, W, NH, R2), f32(LANES, W, NH, DC)
    k_e, c_k, c_v = (f32(n_blocks * BS, NKV * R2), f32(n_blocks * BS, DC),
                     f32(n_blocks * BS, DC))
    lengths = rng.integers(200, mb * BS - 63, LANES).astype(np.int32)
    bt = jnp.asarray(rng.permutation(n_blocks)[:LANES * mb]
                     .reshape(LANES, mb), jnp.int32)
    lens = jnp.asarray(lengths)
    offs = jnp.asarray(lengths - W)
    blk = c_k.reshape(n_blocks, BS, DC)
    sel = ops.select_topk_blocks(q_lat, blk.mean(1), jnp.abs(blk).max(1), bt,
                                 lens, block_size=BS, num_sel=8, recent=2)
    k8, c_k8, c_v8 = (quant.quantize_rows(a) for a in (k_e, c_k, c_v))
    q8_pages = (k8[0], c_k8[0], c_v8[0], k8[1], c_k8[1], c_v8[1])
    kw = dict(q_group=G, scale=scale, block_size=BS)
    cases = {
        "elite_decode_paged": (q_e, q_lat, k_e, c_k, c_v, bt, lens),
        "elite_decode_paged_q8": (q_e, q_lat, *q8_pages, bt, lens),
        "elite_decode_sparse_paged": (q_e, q_lat, k_e, c_k, c_v, *sel),
        "elite_decode_sparse_paged_q8": (q_e, q_lat, *q8_pages, *sel),
        "elite_verify_paged": (qv_e, qv_lat, k_e, c_k, c_v, bt, offs, lens),
        "elite_verify_paged_q8": (qv_e, qv_lat, *q8_pages, bt, offs, lens),
    }
    return cases, kw


def kernel_parity(seed: int):
    import numpy as np
    from repro.kernels import ops, ref

    cases, kw = kernel_cases(seed)
    for name, args in cases.items():
        got = np.asarray(getattr(ops, name)(*args, **kw))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(getattr(ref, name + "_ref")(*args, **kw))
        err = float(np.max(np.abs(got - want)))
        observe(f"parity_max_abs_err[{name}]", err)
        observe(f"parity_max_abs_ref[{name}]", float(np.max(np.abs(want))))
        np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=KERNEL_TOL,
                                   err_msg=name)


# -- (c)-(e) serving legs ----------------------------------------------------

def _int_spec(*shape):
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def assert_kernel_in_steps(sched, verify: bool):
    """(e) The leg's compiled decode (and verify) program calls the paged
    Pallas kernel: the step the scheduler ran is the kernel path."""
    B, mb = sched.scfg.max_slots, sched.scfg.max_blocks_per_seq
    steps = {"decode": sched._decode.lower(
        sched.params, sched.buffers, _int_spec(B, 1), sched.pool.pages,
        _int_spec(B), _int_spec(B, mb), _int_spec(B))}
    if verify:
        W = sched.scfg.speculate_k + 1
        steps["verify"] = sched._verify.lower(
            sched.params, sched.buffers, _int_spec(B, W), sched.pool.pages,
            _int_spec(B, W), _int_spec(B, mb), _int_spec(B), _int_spec(B))
    for name, lowered in steps.items():
        hlo = lowered.compile().as_text()
        n = hlo.count("tpu_custom_call")
        observe(f"tpu_custom_call_in_{name}_step", n)
        assert n > 0, f"the compiled {name} step holds no Pallas kernel"


def kernel_vs_xla_step(sched, seed: int):
    """(e) One decode step over the leg's pool pages, with the Pallas kernel
    and with the XLA gather fallback: the logits agree."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm

    scfg, pool = sched.scfg, sched.pool
    rng = np.random.default_rng(seed)
    B, mb = scfg.max_slots, scfg.max_blocks_per_seq
    tokens = jnp.asarray(rng.integers(0, sched.cfg.vocab_size, (B, 1)),
                         jnp.int32)
    bt = jnp.asarray(rng.integers(0, pool.num_blocks, (B, mb)), jnp.int32)
    lengths = jnp.asarray(rng.integers(BS, mb * BS, B), jnp.int32)
    drop = jnp.full((B,), pool.oob_slot, jnp.int32)   # reads only
    logits = {}
    for use_kernel in (True, False):
        step = jax.jit(lambda p, b, t, pages, sm, tbl, ln, k=use_kernel:
                       lm.apply_decode_paged(p, b, sched.cfg, {"tokens": t},
                                             pages, sm, tbl, ln,
                                             block_size=scfg.block_size,
                                             use_kernel=k)[0])
        logits[use_kernel] = np.asarray(step(sched.params, sched.buffers,
                                             tokens, pool.pages, drop, bt,
                                             lengths))
    scale = float(np.max(np.abs(logits[False])))
    err = float(np.max(np.abs(logits[True] - logits[False])))
    observe("kernel_vs_xla_max_abs_logit_diff", err)
    observe("kernel_vs_xla_logit_scale", scale)
    observe("kernel_vs_xla_argmax_agree",
            float(np.mean(logits[True].argmax(-1) == logits[False].argmax(-1))))
    assert np.all(np.isfinite(logits[True])), "non-finite kernel logits"
    assert err <= LOGIT_TOL * scale, (err, scale)


def serve_leg(name, flags, seed, log, verify: bool):
    from repro.launch import serve

    print(f"== {name} ==", flush=True)
    t0 = time.perf_counter()
    report, sched = serve.main(MODEL + TRAFFIC + flags + ["--seed", str(seed)])
    observe(f"{name}_wall_s_incl_compile", time.perf_counter() - t0)
    cfg = sched.cfg
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == FULL_WIDTH
    observe(f"{name}_requests_completed", report.completed)
    observe(f"{name}_tokens_out", report.decoded_tokens)
    assert report.completed == 8, report.summary()
    assert_kernel_in_steps(sched, verify)
    kernel_vs_xla_step(sched, seed)
    log.report(["_prefill_batch", "_decode", "_verify"] if verify
               else ["_prefill_batch", "_decode"])
    return report


def one_chip(seed: int, log: CompileLog):
    print("== kernel parity ==", flush=True)
    kernel_parity(seed)
    one = serve_leg("leg_one", LEG_ONE, seed, log, verify=False)
    observe("leg_one_preemptions", one.preemptions)
    observe("leg_one_swap_outs", one.swap_outs)
    observe("leg_one_prefix_cache_hits", one.prefix_cache_hits)
    assert one.preemptions >= 1 and one.swap_outs >= 1, one.summary()
    assert one.prefix_cache_hits >= 1, one.summary()
    two = serve_leg("leg_two", LEG_TWO, seed, log, verify=True)
    observe("leg_two_draft_accepted", f"{two.draft_accepted}/"
                                      f"{two.draft_proposed}")


# -- four chips: the sharded serving path --------------------------------------

def _stream_tokens(seed: int, tp: int, dp: int):
    """Serve the leg-one requests greedily at (tp, dp) through launch/serve.py;
    → (uid → tokens, the devices each replica's pool and weights live on)."""
    from repro.launch import serve

    t0 = time.perf_counter()
    report, server = serve.main(MODEL + TRAFFIC + LEG_ONE + [
        "--seed", str(seed), "--tp", str(tp), "--dp", str(dp)])
    observe(f"tp{tp}_dp{dp}_wall_s_incl_compile", time.perf_counter() - t0)
    reps = server.replicas if dp > 1 else [server]
    tokens = {}
    for rep in reps:
        tokens.update({r.uid: list(r.generated) for r in rep.finished})
    devices = [sorted({d.id for leaf in jax.tree.leaves((rep.pool.pages,
                                                          rep.params))
                       for d in leaf.devices()}) for rep in reps]
    return tokens, devices


def tp_kernel_identity(seed: int):
    """Each tensor-parallel kernel wrapper at tp=4 and tp=2 against the same
    kernel on one chip: observed, bitwise, per kernel (the layer under the
    token-identity wall)."""
    import numpy as np
    from repro.kernels import ops
    from repro.launch.mesh import make_serving_mesh, replica_meshes

    cases, kw = kernel_cases(seed)
    for tp in (4, 2):
        mesh = replica_meshes(make_serving_mesh(tp=tp))[0]
        for name, args in cases.items():
            q8 = name.endswith("_q8")
            scales = args[5:8] if q8 else None
            walk = args[8:] if q8 else args[5:]
            tp_fn = getattr(ops, name.removesuffix("_q8") + "_tp")
            got = np.asarray(tp_fn(*args[:5], scales, *walk, **kw, mesh=mesh))
            want = np.asarray(getattr(ops, name)(*args, **kw))
            observe(f"tp{tp}_bitwise[{name}]",
                    f"{np.array_equal(got, want)} "
                    f"(max abs diff {float(np.max(np.abs(got - want)))})")


def four_chips(seed: int):
    assert len(jax.devices()) >= 4, jax.devices()
    tp_kernel_identity(seed)
    single, _ = _stream_tokens(seed, 1, 1)
    assert len(single) == 8
    failed = []
    for tp, dp in ((4, 1), (2, 2)):
        got, devices = _stream_tokens(seed, tp, dp)
        observe(f"tp{tp}_dp{dp}_replica_devices", devices)
        flat = [d for ds in devices for d in ds]
        if not len(flat) == len(set(flat)) == tp * dp:
            failed.append(f"tp={tp} dp={dp} replicas share devices")
        differ = [u for u, t in single.items() if got.get(u) != t]
        observe(f"tp{tp}_dp{dp}_streams_identical_to_one_chip",
                f"{len(single) - len(differ)}/{len(single)}")
        for u in differ:
            first = next((i for i, (a, b) in enumerate(zip(single[u], got[u]))
                          if a != b), None)
            observe(f"tp{tp}_dp{dp}_uid{u}_first_different_token", first)
        if differ:
            failed.append(f"tp={tp} dp={dp} streams differ: uids {differ}")
    assert not failed, failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path (tp=4, tp=2 x dp=2) "
                         "against one chip; needs four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]                                  # (a)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}); this script runs only on a TPU")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.launch import compile_cache

    observe("compile_cache_dir", compile_cache.enable())
    log = CompileLog()
    observe("device_kind", dev.device_kind)
    observe("device_count", len(jax.devices()))
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed, log)
    observe("persistent_cache_events", dict(log.events))
    observe("peak_bytes_in_use", (dev.memory_stats() or {}).get(
        "peak_bytes_in_use", "not reported"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
