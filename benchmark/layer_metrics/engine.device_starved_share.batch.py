"""Device idle between passes while the engine had work to run (every
``idle_cause`` of ``serve.pass`` but ``no_work``) over ``window_s``."""

import pass_readers


def read(ctx):
    return pass_readers.starved_share(ctx)
