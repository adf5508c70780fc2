"""Share of the step loop's wall time not spent waiting for the device."""

import readers


def read(ctx):
    return readers.host_share(ctx)
