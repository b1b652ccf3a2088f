"""Refactor layer: the summed stall, in ms, of every ``engine.refactor``
the controller ordered in the window (each call's own host-clock report).
Nothing to read where the cell runs no controller."""


def read(run):
    if not run.controller:
        return None
    return 1e3 * sum(ev["t"] for ev in run.refactors)
