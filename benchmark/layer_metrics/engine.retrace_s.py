"""Of ``engine.warmup_s``, the seconds spent tracing and lowering programs
afresh (``serve.resolve.build`` with source ``trace``): what a start pays
again for programs it has compiled before."""

import span_readers


def read(ctx):
    return span_readers.resolve_s(ctx, "serve.resolve.build", "trace")
