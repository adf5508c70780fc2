"""Log-normal: ``median``, ``sigma`` (``min``/``max`` clip in ``traffic``)."""

import math
import statistics

_NORMAL = statistics.NormalDist()


def at(spec, qs, rate=None):
    mu = math.log(spec["median"])
    return [math.exp(mu + spec["sigma"] * _NORMAL.inv_cdf(q)) for q in qs]
