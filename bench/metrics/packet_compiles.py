"""Jit lowerings the runtime counted inside packets over the window
(``RunResult.compiles``, keyed by group and packet size, summed over
submits).  Set-up warms every packet size, so the count should be 0.
None where the runtime does not report it."""


def read(run):
    xs = [getattr(s.result, "compiles", None) for s in run.submits]
    if not xs or None in xs:
        return None
    return float(sum(sum(c.values()) for c in xs))
