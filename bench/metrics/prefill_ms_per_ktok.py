"""Model step, prefill: device time of the stage-prefill programs
(``jit_prefill``) in the traced window, in ms per 1,000 prompt tokens
admitted in it."""


def read(run):
    if run.trace is None:
        return None
    dev_s = sum(run.trace.modules_named("jit_prefill"))
    toks = sum(s.prompt_tokens for s in run.steps)
    return 1e6 * dev_s / toks if dev_s and toks else None
