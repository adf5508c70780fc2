"""Log-uniform between ``min`` and ``max``."""

import math


def at(spec, qs, rate=None):
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    return [math.exp(lo + (hi - lo) * q) for q in qs]
