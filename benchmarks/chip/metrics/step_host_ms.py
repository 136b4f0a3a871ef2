"""Engine: the host's own work per step (ms), the median over the traced
window's ``serve.step`` spans of the step's duration less its
``serve.*.sync`` spans, in which the host waits for the device."""

import numpy as np


def read(run):
    steps = (run.trace or {}).get("phases")
    if not steps:
        return None
    own = [p["serve.step"] - sum(v for k, v in p.items() if k.endswith(".sync"))
           for p in steps]
    return 1e3 * float(np.median(own))
