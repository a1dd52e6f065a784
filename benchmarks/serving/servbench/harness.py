"""One run of one cell: set up, serve traffic for a measured window, check
what was served, print one JSON line.

The system under test is ``repro.runtime.serve_loop.Scheduler`` over its
``PagedKVPool``, driven only through ``submit`` and ``step``.  The harness
keeps its own clock: a request is due when its client sends it (open loop:
its scheduled time; closed loop: when the client's previous request
finished), and each token is stamped when the ``step`` that produced it
returns.  From the scheduler it reads the phase counters of its metrics
registry, the prefix cache's counters and the requests' public fields.
Model code (sizes, weights, the plain reference, work counts) it reaches
only through the configuration's model module
(``servbench/models/<model>.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import spec as speclib
from . import traffic as trafficlib

TRACE_SECONDS = 4.0            # the traced sub-window of a --trace 1 run
clock = time.perf_counter


class NoAccelerator(RuntimeError):
    pass


# -- compilations -------------------------------------------------------------

class CompileLog:
    """Counts compilations (and persistent-cache hits, each a trace and a
    lowering) from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.compiles + self.cache_hits


# -- the cell -----------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """One cell; its model module and sizes follow from ``conf["model"]``."""
    name: str
    chips: int
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    check: Dict[str, Any]
    e2e: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def __post_init__(self):
        self.model = speclib.model(self.conf["model"])
        self.dims = self.model.dims(self.conf)

    @classmethod
    def load(cls, name: str) -> "Cell":
        bench = speclib.benchmark()
        w = speclib.workload(bench, name)
        return cls(name=name, chips=w["chips"],
                   conf=speclib.config(w["config"]),
                   mix=speclib.traffic(w["traffic"]),
                   check=speclib.load_json(speclib.HERE / "checks"
                                           / f"{name}.json"),
                   e2e=speclib.metrics_for(bench, "end_to_end", name),
                   per_layer=speclib.metrics_for(bench, "per_layer", name))


def scheduler_config(conf: Dict[str, Any], mix: Dict[str, Any]):
    from repro.runtime.serve_loop import SchedulerConfig
    import jax.numpy as jnp
    s = mix["scheduler"]
    return SchedulerConfig(
        max_slots=s["max_slots"], block_size=conf["block_size"],
        num_blocks=conf["num_blocks"], max_new_tokens=s["max_len"],
        max_len=s["max_len"],
        prefill_chunk_tokens=s["prefill_chunk_tokens"],
        prefill_batch_lanes=s["prefill_batch_lanes"],
        prefix_cache=s["prefix_cache"], eviction=s["eviction"],
        cache_dtype=jnp.dtype(conf["pool_dtype"]))


def build_scheduler(cell: Cell, params, buffers):
    """The system under test: one ``Scheduler`` over the cell's weights."""
    from repro.obs.metrics import MetricsRegistry
    from repro.runtime.serve_loop import Scheduler
    return Scheduler(params, buffers, cell.model.program_config(cell.conf),
                     scheduler_config(cell.conf, cell.mix),
                     metrics=MetricsRegistry())


# -- bookkeeping of served requests --------------------------------------------

@dataclasses.dataclass
class Rec:
    req: Any
    client: int
    due: float
    sent: float
    first: Optional[float] = None
    last: Optional[float] = None
    seen: int = 0
    done: Optional[float] = None
    rejected: bool = False           # the scheduler refused it at submit


@dataclasses.dataclass
class Window:
    """Everything the window measured, on the host clock."""
    start: float = 0.0
    end: float = 0.0
    steps: int = 0
    step_wall_s: float = 0.0
    tokens: int = 0
    gaps: List[float] = dataclasses.field(default_factory=list)
    due: List[Rec] = dataclasses.field(default_factory=list)
    finished: List[Rec] = dataclasses.field(default_factory=list)
    counters0: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters1: Dict[str, float] = dataclasses.field(default_factory=dict)
    # the work of each step (traced sub-window only)
    decode_lengths: List[List[int]] = dataclasses.field(default_factory=list)
    model_flops: float = 0.0
    # pool blocks held (prefix-cache blocks kept for reuse included)
    blocks_peak: int = 0
    blocks_sum: int = 0
    lanes0: int = 0                  # busy slots as the window opens
    lanes1: int = 0                  # and as it closes

    def delta(self, key: str) -> float:
        return self.counters1.get(key, 0.0) - self.counters0.get(key, 0.0)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def failed(self) -> int:
        """Requests due in the window that were refused, or that finished
        with fewer tokens than their budget.  One still unserved when the
        window closes is late, not failed."""
        return sum(1 for r in self.due if r.rejected or (
            r.done is not None
            and len(r.req.generated) < r.req.max_new_tokens))


class Server:
    """A scheduler under the mix's load, with the client side kept here."""

    def __init__(self, cell: Cell, sched, mix: trafficlib.Mix):
        self.cell, self.sched, self.mix = cell, sched, mix
        self.closed = cell.mix["loop"] == "closed"
        self.recs: Dict[int, Rec] = {}
        self.uid = 0
        self.n_finished = 0
        self.window: Optional[Window] = None
        self.count_work = False
        self._stream = mix.requests() if self.closed else None
        self._arrivals = None if self.closed else mix.arrivals()
        self._next: Optional[Tuple[float, np.ndarray, int]] = None
        self._origin = 0.0          # open loop: when the schedule starts
        self._resend: List[Tuple[int, float]] = []   # closed: (client, due)

    # -- requests ------------------------------------------------------------
    def send(self, prompt: np.ndarray, budget: int, client: int,
             due: float) -> Rec:
        from repro.runtime.serve_loop import OutOfBlocks, Request
        req = Request(uid=self.uid, prompt=prompt, max_new_tokens=budget,
                      arrival=float(self.sched.t))
        self.uid += 1
        rec = Rec(req=req, client=client, due=due, sent=clock())
        try:
            self.sched.submit(req)
        except OutOfBlocks:
            rec.rejected = True
            if self.closed and client >= 0:      # the client sends its next
                self._resend.append((client, rec.sent))
        self.recs[req.uid] = rec
        if self.window is not None and due >= self.window.start:
            self.window.due.append(rec)
        return rec

    def send_due(self, now: float) -> None:
        if self.closed:
            pending, self._resend = self._resend, []
            for client, due in pending:
                prompt, budget = next(self._stream)
                self.send(prompt, budget, client, due)
            return
        while True:
            if self._next is None:
                self._next = next(self._arrivals)
            off, prompt, budget = self._next
            if self._origin + off > now:
                return
            self.send(prompt, budget, -1, self._origin + off)
            self._next = None

    def next_due(self) -> float:
        if self._next is None:
            self._next = next(self._arrivals)
        return self._origin + self._next[0]

    def lanes(self) -> int:
        """Slots holding a request."""
        return sum(s is not None for s in self.sched.slots)

    def busy(self) -> bool:
        return bool(self.sched.waiting) or self.lanes() > 0

    # -- one step --------------------------------------------------------------
    def step(self, annotate) -> None:
        before = self._snapshot() if self.count_work else None
        with annotate("bench.step"):
            s0 = clock()
            self.sched.step()
            now = clock()
        with annotate("bench.record"):
            w = self.window
            if w is not None:
                w.steps += 1
                w.step_wall_s += now - s0
                held = self.sched.pool.allocator.num_used
                w.blocks_peak = max(w.blocks_peak, held)
                w.blocks_sum += held
            self._observe(now, before)

    def _live(self):
        for s in self.sched.slots:
            if s is not None:
                yield s
        fin = self.sched.finished
        for r in fin[self.n_finished:]:
            yield r

    def _snapshot(self):
        return {r.uid: (len(r.generated), r.prefill_pos, r.prefix_hit_tokens)
                for r in list(self._live()) + list(self.sched.waiting)}

    def _observe(self, now: float, before) -> None:
        w = self.window
        lengths = []
        for req in self._live():
            rec = self.recs[req.uid]
            n = len(req.generated)
            if n > rec.seen:
                new = n - rec.seen
                if rec.first is None:
                    rec.first = now
                if w is not None:
                    w.tokens += new
                    if rec.last is not None and rec.last >= w.start:
                        w.gaps.append(now - rec.last)
                        w.gaps.extend([0.0] * (new - 1))
                rec.last = now
                rec.seen = n
            if before is not None:
                lengths += self._count_work(req, before.get(req.uid))
        if before is not None:
            w.decode_lengths.append(lengths)
        fin = self.sched.finished
        for req in fin[self.n_finished:]:
            rec = self.recs[req.uid]
            rec.done = now
            if w is not None:
                w.finished.append(rec)
            if self.closed and rec.client >= 0:
                self._resend.append((rec.client, now))
        self.n_finished = len(fin)

    def _count_work(self, req, was) -> List[int]:
        """Model FLOPs of what ``req`` advanced by in the last step; returns
        the context lengths its decode tokens attended over."""
        m, model = self.cell.dims, self.cell.model
        g0, pos0, hit0 = was if was is not None else (0, 0, 0)
        g1 = len(req.generated)
        hit = req.prefix_hit_tokens - hit0
        start = pos0 + hit
        n_pre = max(0, req.prefill_pos - start) if req.prefill_pos >= pos0 \
            else 0
        self.window.model_flops += model.prefill_flops(m, start, n_pre)
        finished_prefill = n_pre > 0 and req.prefill_pos >= len(
            req.prefill_source())
        n_dec = g1 - g0 - (1 if finished_prefill else 0)
        if finished_prefill:
            self.window.model_flops += model.head_flops(m)
        ctx = []
        for k in range(n_dec):
            n = len(req.prompt) + g1 - n_dec + k
            ctx.append(n)
            self.window.model_flops += model.decode_token_flops(m, n)
        return ctx

    # -- phases of the run -----------------------------------------------------
    def drain(self, annotate, until: Callable[[], bool]) -> None:
        """Step (closed loops keep sending) until ``until()`` holds."""
        while not until():
            if self.closed:
                self.send_due(clock())
            self.step(annotate)

    def serve(self, seconds: float, annotate) -> Window:
        """Serve the mix for ``seconds``; the window closes with the step
        that runs past its end."""
        w = self.window = Window()
        w.lanes0 = self.lanes()
        w.counters0 = self.counters()
        w.start = clock()
        if not self.closed and self._origin == 0.0:
            self._origin = w.start
        end = w.start + seconds
        while True:
            now = clock()
            if now >= end:
                break
            with annotate("bench.send"):
                self.send_due(now)
            if not self.closed and not self.busy():
                with annotate("bench.wait"):
                    time.sleep(max(0.0, min(self.next_due(), end) - now))
                continue
            self.step(annotate)
        w.end = clock()
        w.lanes1 = self.lanes()
        w.counters1 = self.counters()
        self.window = None
        return w

    def counters(self) -> Dict[str, float]:
        reg = self.sched.metrics
        out = {}
        for p in ("prefill", "decode", "draft", "verify", "sample", "swap",
                  "other"):
            inst = reg.get(f"serve_phase_{p}_ms_total")
            out[f"phase_{p}_ms"] = inst.value if inst is not None else 0.0
        for key, name in (("prefill_tokens", "serve_prefill_tokens_total"),
                          ("prefill_calls", "serve_prefill_calls_total"),
                          ("prefill_capacity_tokens",
                           "serve_prefill_capacity_tokens_total"),
                          ("steps", "serve_steps_total"),
                          ("step_wall_ms", "serve_step_wall_ms_total")):
            out[key] = float(reg.get(name).value)
        out["decode_steps"] = float(reg.get("serve_step_ms").count)
        pc = self.sched.bm.prefix
        out["hit_tokens"] = float(pc.hit_tokens) if pc is not None else 0.0
        out["lookup_tokens"] = float(pc.lookup_tokens) if pc is not None \
            else 0.0
        return out


# -- results --------------------------------------------------------------------

def percentile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if xs else None


def end_to_end(w: Window) -> Dict[str, Optional[float]]:
    ttft = []
    for rec in w.due:
        if rec.first is not None and rec.first <= w.end:
            ttft.append(rec.first - rec.due)
        else:
            ttft.append(w.end - rec.due)     # still waiting: its wait so far
    return {
        "output_tok_s": w.tokens / w.seconds,
        "ttft_p90_ms": (percentile(ttft, 90) or 0.0) * 1e3 if ttft else None,
        "itl_p95_ms": percentile(w.gaps, 95) * 1e3 if w.gaps else None,
    }


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader may read."""
    dims: Any                            # the model module's sizes
    peaks: Dict[str, Any]                # one chip's
    window: Window                       # the untraced measured window
    traced: Optional[Window] = None      # the traced sub-window's host side
    trace: Any = None                    # xtrace.Reduced of the sub-window
    model: Any = None                    # the model module
    chips: int = 1                       # chips the cell's work runs on


def _annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX has "
                            f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def sample_checked(finished: List[Rec], seed: int, check: Dict) -> List[Rec]:
    """The requests whose served tokens are checked: the one with the most
    served tokens, then others drawn from the seed until ``min_tokens``
    served tokens or ``max_requests`` requests."""
    recs = sorted(finished, key=lambda r: (-len(r.req.generated), r.req.uid))
    if not recs:
        return []
    out, rest = [recs[0]], recs[1:]
    order = np.random.default_rng((seed, 7)).permutation(len(rest))
    total = len(recs[0].req.generated)
    for i in order:
        if total >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(rest[i])
        total += len(rest[i].req.generated)
    return out


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the run --------------------------------------------------------------------

def warm_up(server: Server, seed: int) -> None:
    """One request of two chunks (a fresh and a resumed one) and two decode
    steps: it goes through every program the window uses."""
    cell = server.cell
    chunk = cell.mix["scheduler"]["prefill_chunk_tokens"]
    warm = np.random.default_rng((seed, 9)).integers(
        0, cell.dims.vocab, chunk + 1, dtype=np.int64).astype(np.int32)
    rec = server.send(warm, 3, -1, clock())
    server.drain(_annotator(False), lambda: rec.done is not None)


def setup_and_serve(cell: Cell, seed: int, seconds: float, trace: bool,
                    t_process: float, clog: CompileLog):
    """Everything that needs the program: returns host data only, so the
    program's device state is freed when it returns."""
    import jax
    from repro.launch import compile_cache

    parts = {"interpreter_and_jax_init_s": clock() - t_process}
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache_dir}")

    t = clock()
    params, buffers = cell.model.make(cell.dims, seed,
                                      jax.numpy.dtype(cell.conf["dtype"]))
    jax.block_until_ready((params, buffers))
    parts["weights_s"] = clock() - t

    t = clock()
    sched = build_scheduler(cell, params, buffers)
    del params, buffers
    jax.block_until_ready(sched.pool.pages)
    parts["pool_s"] = clock() - t

    mix = trafficlib.Mix(cell.mix, cell.dims.vocab, seed)
    server = Server(cell, sched, mix)
    off = _annotator(False)

    t = clock()
    warm_up(server, seed)
    parts["warmup_s"] = clock() - t

    t = clock()
    docs = mix.documents()
    doc_recs = [server.send(d, 1, -1, clock()) for d in docs]
    server.drain(off, lambda: all(r.done is not None for r in doc_recs))
    initial = [server.send(p, b, c if server.closed else -1, clock())
               for c, (p, b) in enumerate(mix.initial())]
    server.drain(off, lambda: all(r.first is not None for r in initial))
    parts["context_fill_s"] = clock() - t
    for k, v in parts.items():
        log(f"setup: {k} = {v}")

    compiles0 = clog.count()
    setup_s = clock() - t_process
    w = server.serve(seconds, off)
    compiles = clog.count() - compiles0
    log(f"window: {w.seconds} s, {w.steps} steps, {w.tokens} tokens, "
        f"{len(w.due)} requests due, {len(w.finished)} finished, "
        f"{compiles} compilations inside the window")
    log(f"busy slots: {w.lanes0} as the window opened, {w.lanes1} as it "
        f"closed")
    log(f"pool: {w.blocks_sum / max(w.steps, 1)} blocks held on average, "
        f"{w.blocks_peak} at most, of {cell.conf['num_blocks']}")

    traced, reduced = None, None
    if trace:
        traced, reduced = traced_window(server)

    mem = memory_peak()
    checked = [(np.asarray(r.req.prompt), list(r.req.generated))
               for r in sample_checked(w.finished, seed, cell.check)]
    return dict(setup_s=setup_s, window=w, traced=traced, reduced=reduced,
                memory_peak=mem, checked=checked, compiles=compiles)


def traced_window(server: Server, seconds: float = TRACE_SECONDS,
                  keep_in: Optional[str] = None):
    """Serve on for ``seconds`` under the profiler and reduce its trace;
    the trace is deleted unless ``keep_in`` names a directory for it."""
    import jax
    from . import xtrace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = keep_in or tempfile.mkdtemp(prefix="bench_trace_")
    try:
        server.count_work = True
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
                traced = server.serve(seconds, _annotator(True))
        finally:
            jax.profiler.stop_trace()
            server.count_work = False
        files = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                 for f in fs if f.endswith(".xplane.pb")]
        reduced = xtrace.reduce(files[0])
    finally:
        if keep_in is None:
            shutil.rmtree(tmp, ignore_errors=True)
    return traced, reduced


def check_served(cell: Cell, seed: int, checked,
                 control: bool = False) -> Dict[str, Any]:
    """Reference logits over the checked requests: the widest gap by which a
    served token's logit lies below the reference's best.  ``control``
    judges instead the token the float8-operand forward puts first at each
    row, as the control does."""
    if not checked:
        return {"max_logit_gap": None, "tokens": 0, "requests": 0}
    params, buffers = cell.model.make(cell.dims, seed)
    gaps = cell.model.served_gaps(
        cell.dims, params, buffers, checked,
        cell.mix["scheduler"]["max_len"], control=control)
    del params, buffers
    allg = np.concatenate(gaps)
    return {"max_logit_gap": float(allg.max()), "tokens": int(allg.size),
            "requests": len(checked)}


def is_correct(chk: Dict[str, Any], limit: float) -> bool:
    return chk["max_logit_gap"] is not None and chk["max_logit_gap"] <= limit


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_process: float) -> Dict[str, Any]:
    return run_cell(Cell.load(cell_name), seed, seconds, trace, t_process)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float) -> Dict[str, Any]:
    device = device_info(cell.chips)
    clog = CompileLog()
    out = setup_and_serve(cell, seed, seconds, trace, t_process, clog)
    gc.collect()

    t = clock()
    chk = check_served(cell, seed, out["checked"])
    limit = cell.check["max_logit_gap"]
    correct = is_correct(chk, limit)
    log(f"check: {chk['requests']} requests, {chk['tokens']} served tokens, "
        f"reference {clock() - t} s")

    w = out["window"]
    device = dict(device, memory_peak_bytes=out["memory_peak"])
    if not trace:
        values = end_to_end(w)
        values["setup_s"] = out["setup_s"]
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.e2e if values.get(m["name"]) is not None}
        breakdown = None
    else:
        peaks = speclib.peaks(device["kind"])
        ctx = Ctx(dims=cell.dims, peaks=peaks, window=w,
                  traced=out["traced"], trace=out["reduced"],
                  model=cell.model, chips=cell.chips)
        metrics = {}
        for m in cell.per_layer:
            v = speclib.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        red = out["reduced"]
        device.update(busy_s=red.busy_s(), window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.idle_by_host(10)}
    result = {"correct": bool(correct), "attempted": len(w.due),
              "failed": w.failed(),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {"max_logit_gap": {"value": chk["max_logit_gap"],
                                         "limit": limit}}
    return result
