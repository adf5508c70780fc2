"""Device seconds of the prefill and chunk parts over all parts' device
seconds (``serve.pass``), whole window: ``program.prefill_share.chat``
from the program's own clock reads."""

import pass_readers


def read(ctx):
    return pass_readers.prefill_pass_share(ctx)
