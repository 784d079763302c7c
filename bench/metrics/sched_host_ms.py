"""Milliseconds of the scheduler's own host work per mixed step over the
window, from the program's telemetry phases: ``mixed_step.prepare``
(token blocks, page tables) and ``mixed_step.commit`` (tokens,
retirements, metrics) of every step, and the time of ``admit`` less its
weight walks (``admit.walk``), over the count of ``mixed_step``."""

PHASES = ("mixed_step", "mixed_step.prepare", "mixed_step.commit")


def read(run):
    ph = run.phases
    if not all(p in ph for p in PHASES):
        return None

    def total(name):
        return ph[name].total if name in ph else 0.0

    host = (total("mixed_step.prepare") + total("mixed_step.commit")
            + total("admit") - total("admit.walk"))
    return host / ph["mixed_step"].n * 1e3
