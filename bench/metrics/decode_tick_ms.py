"""Model step, decode: mean device time, in ms, of one fused decode tick
(the ``jit_tick`` program) in the traced window."""


def read(run):
    if run.trace is None:
        return None
    ticks = run.trace.modules_named("jit_tick")
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
