"""What the per-layer metrics compute; each ``layer_metrics/<name>.py``
names one of these.  A reader returns None where its cell has nothing for
it to read, and the metric is then left out of the line."""
from __future__ import annotations

from typing import Optional

from . import work

DECODE_KERNEL = "elite_decode_paged"      # the Pallas kernel's name
FORWARD_PHASES = ("prefill", "decode", "draft", "verify")


def host_ms_per_step(ctx) -> Optional[float]:
    """Scheduler host time per step: the step's wall time less its forward
    phases (prefill, decode, draft, verify), ms."""
    w = ctx.window
    if not w.steps:
        return None
    fwd = sum(w.delta(f"phase_{p}_ms") for p in FORWARD_PHASES)
    return (w.step_wall_s * 1e3 - fwd) / w.steps


def prefix_hit_rate(ctx) -> Optional[float]:
    """Share of prompt tokens looked up that the prefix cache served, %."""
    w = ctx.window
    looked = w.delta("lookup_tokens")
    return 100.0 * w.delta("hit_tokens") / looked if looked else None


def decode_ms(ctx) -> Optional[float]:
    """Wall time of one decode forward (to its block_until_ready), ms."""
    w = ctx.window
    n = w.delta("decode_steps")
    return w.delta("phase_decode_ms") / n if n else None


def prefill_ms_per_ktok(ctx) -> Optional[float]:
    """Prefill forward wall time per thousand prompt tokens, ms."""
    w = ctx.window
    toks = w.delta("prefill_tokens")
    return w.delta("phase_prefill_ms") / (toks / 1e3) if toks else None


def prefill_fill(ctx) -> Optional[float]:
    """Share of the prefill calls' token rows that held prompt tokens, the
    rest being padding, %."""
    w = ctx.window
    rows = w.delta("prefill_capacity_tokens")
    return 100.0 * w.delta("prefill_tokens") / rows if rows else None


def decode_attn_roofline(ctx) -> Optional[float]:
    """The decode-attention kernel's share of its roofline in the traced
    sub-window: least time for the algorithm's operations and bytes (the
    model module's count, on one chip's peaks) over the kernel's device
    time summed over the chips, %."""
    if ctx.trace is None or ctx.traced is None:
        return None
    kernel_s = ctx.trace.op_seconds(DECODE_KERNEL) * ctx.chips
    if kernel_s <= 0:
        return None
    least = 0.0
    for lengths in ctx.traced.decode_lengths:
        if lengths:
            f, b = ctx.model.decode_attn_work(ctx.dims, lengths)
            least += work.roofline_seconds(f, b, ctx.peaks)
    return 100.0 * least / kernel_s


def idle_share(ctx) -> Optional[float]:
    """Share of the traced sub-window with no operation on the device, %."""
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()


def mfu(ctx) -> Optional[float]:
    """Model FLOP/s of the traced sub-window's work over the peak of the
    cell's chips, %."""
    if ctx.trace is None or ctx.traced is None:
        return None
    if ctx.traced.model_flops <= 0:
        return None
    return 100.0 * ctx.traced.model_flops / (
        ctx.trace.window_s * ctx.chips * ctx.peaks["flops_per_s"])
