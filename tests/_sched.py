"""A serial RAS scheduler loaded with LP requests, the reference state
the parity tests export to the jitted placement path."""

import numpy as np

from repro.core.scheduler import RASScheduler
from repro.core.tasks import LPRequest, Priority, Task


def loaded_scheduler(seed: int, n_req: int = 3, n_devices: int = 4):
    """``n_req`` two-task LP requests, released at seeded times in
    [0, 30) s on devices 0, 1, 2, ... in turn, each with a 60 s
    deadline."""
    s = RASScheduler(n_devices, 20e6, seed=seed)
    rng = np.random.default_rng(seed)
    for i in range(n_req):
        t = float(rng.uniform(0, 30))
        src = i % n_devices
        req = LPRequest(
            [Task(Priority.LOW, src, t, t + 60.0, 0) for _ in range(2)],
            src, t,
        )
        s.schedule_lp(req, t)
    return s
