"""High-water of KV blocks in use over the window, as a share of all
blocks (``eng.blocks.utilization()`` sampled as each step returns)."""


def read(ctx):
    util = [s[4] for s in ctx.get("steps", ())]
    return 100.0 * max(util) if util else None
