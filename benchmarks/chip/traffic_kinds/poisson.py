"""Open-loop Poisson arrivals at a fixed rate.

Traffic file keys: "rate_per_s", "warm_in_s", "prompt", "output" (length
distributions, see benchlib/traffic.py), optionally "schedule_seed".  The
warm-in and the window each get rate x their length of requests, spread
over them alone, so every run's window is offered the same multiset of
sizes and gaps whatever its seed.
"""

import numpy as np

from benchlib import traffic


def generate(spec: dict, seed: int, seconds: float, vocab: int):
    rate, warm = float(spec["rate_per_s"]), float(spec["warm_in_s"])
    rng = traffic.order_rng(spec, seed)
    t, p, o = [], [], []
    for start, span in ((0.0, warm), (warm, float(seconds))):
        n = traffic.count_for(rate, span)
        t.append(start + traffic.poisson_times(rng, rate, n, span))
        pl, ol = traffic.sizes(rng, n, spec["prompt"], spec["output"])
        p.append(pl)
        o.append(ol)
    return traffic.arrivals(seed, np.concatenate(t), np.concatenate(p),
                            np.concatenate(o), vocab)
