"""Model FLOP/s utilisation of the whole window: the operations this
program's share of the model needs for every real token the window's
steps prefilled or decoded (``arith_branch.window_flops`` over the
driver's own record of each step, held picks at a uniform router's share:
bucket padding and idle time lower it) over the window's length times the
chip's bf16 peak.  The share of the whole step, beside the kernels'."""

import arith_branch


def read(ctx):
    peaks, d, win = ctx.get("peaks"), ctx.get("branch"), ctx.get("window")
    if not peaks or not d or not win or not ctx.get("steps"):
        return None
    if win.get("window_s", 0) <= 0:
        return None
    return 100.0 * arith_branch.window_flops(d, ctx["steps"]) / (
        win["window_s"] * peaks[0])
