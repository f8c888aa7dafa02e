"""Mean wall time of one ``submit(...).result()`` in the window: the sum
of the per-submit times over their count (one caller, closed loop)."""


def read(run):
    walls = [s.wall_s for s in run.submits]
    return sum(walls) / len(walls) if walls else None
