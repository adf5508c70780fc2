"""Exponential arrival gaps of mean 1/rate, rescaled so that the n gaps
sum to exactly n/rate: every run lasts the same."""

import math


def at(spec, qs, rate=None):
    vals = [-math.log(1.0 - q) for q in qs]
    scale = len(vals) / (rate * sum(vals))
    return [v * scale for v in vals]
