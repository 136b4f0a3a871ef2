"""Engine: median wall time of one ``ContinuousEngine.step`` call in the
window (ms)."""

import numpy as np


def read(run):
    steps = [b - a for a, b in run.steps if run.t0 <= a and b <= run.t1]
    return 1e3 * float(np.median(steps)) if steps else None
