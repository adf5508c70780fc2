"""``values`` repeated to length n (one value: a constant)."""


def at(spec, qs, rate=None):
    return [spec["values"][i % len(spec["values"])] for i in range(len(qs))]
