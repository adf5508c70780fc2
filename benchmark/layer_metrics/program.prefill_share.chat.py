"""Prefill + chunk programs' share of device busy time (trace)."""

import readers


def read(ctx):
    return readers.prefill_share(ctx)
