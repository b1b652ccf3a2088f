"""Kernels: the table-walk decode kernel (``paged_decode_attention``) as a
share of its roofline, in %.  The least time the chip could take is the
larger of the bytes it must move (k and v of the live blocks of every
decoding slot, queries and outputs; bench/flops.py) over peak HBM bandwidth
and its operations over peak bf16 rate; the kernel's time is the sum of its
device events in the traced window."""
from bench import flops


def _kernel_s(run) -> float:
    """Device seconds of the table-walk kernel: the Mosaic custom call that
    reads the KV pools, ``(n_blocks, Kh, block_size, hd)``."""
    m, bs = run.model, int(run.serve["block_size"])
    pool = f",{m['Kh']},{bs},{m['hd']}]"
    return sum(sec for name, sec in run.trace.op_seconds.items()
               if name.startswith("tpu_custom_call") and any(
                   pool in t for t in run.trace.op_texts.get(name, ())))


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kern_s = _kernel_s(run)
    n = sum(s.decoded for s in run.steps)
    if not kern_s or not n:
        return None
    bs = int(run.serve["block_size"])
    nbytes = flops.paged_attention_bytes(
        run.model, n, sum(s.live_blocks for s in run.steps), bs)
    ops = flops.paged_attention_flops(run.model,
                                      sum(s.ctx_sum for s in run.steps))
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["bf16_flops_per_s"])
    return flops.roofline_share(least, kern_s)
