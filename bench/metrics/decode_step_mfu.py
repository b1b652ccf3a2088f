"""Device, whole decode tick: model operations of the tokens actually
decoded in the traced window (live slots only: twice the matmul weights of
every layer and of the head, plus attention over each slot's live context;
bench/flops.py) over the ticks' device time at the chip's bf16 peak, in %."""
from bench import flops


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    tick_s = sum(run.trace.modules_named("jit_tick"))
    n = sum(s.decoded for s in run.steps)
    if not tick_s or not n:
        return None
    ops = flops.decode_flops(run.model, n, sum(s.ctx_sum for s in run.steps))
    return flops.roofline_share(ops / run.peaks["bf16_flops_per_s"], tick_s)
