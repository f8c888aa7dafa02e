"""95th percentile of the per-submit wall times of all submits in the
window (numpy's linear interpolation)."""
import numpy as np


def read(run):
    walls = [s.wall_s for s in run.submits]
    return float(np.percentile(walls, 95)) if walls else None
