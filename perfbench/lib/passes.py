"""Device passes in a traced slice, for the readers that give a time per
pass."""


def device_passes(run) -> int:
    """The ``engine.device`` spans (one a cohort's device pass) that end in
    the traced slice of ``run``."""
    lo, hi = run.trace.window
    return sum(1 for sp in run.spans
               if sp.name == "engine.device" and lo <= sp.end_ns * 1e-9 < hi)
