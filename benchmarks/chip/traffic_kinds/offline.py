"""An offline queue: every request is due at t = 0.

Traffic file keys: "requests" (how many; enough that the queue never
empties before the window ends), "prompt", "output" (length
distributions, see benchlib/traffic.py), optionally "schedule_seed", and
"warm_in_s", the seconds from the traffic's start to the window's, in
which the slots fill.
"""

import numpy as np

from benchlib import traffic


def generate(spec: dict, seed: int, seconds: float, vocab: int):
    n = int(spec["requests"])
    p, o = traffic.sizes(traffic.order_rng(spec, seed), n, spec["prompt"],
                         spec["output"])
    return traffic.arrivals(seed, np.zeros(n), p, o, vocab)
