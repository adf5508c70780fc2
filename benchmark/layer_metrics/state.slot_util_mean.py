"""Mean share of the state pool's slots held by admitted requests, over
the window's steps: ``serve.step``'s ``state_slots`` over ``max_batch``.
The pool, not the K/V cache, is what limits concurrency in a hybrid
decoder."""

import span_readers


def read(ctx):
    d = ctx.get("hybrid")
    spans = span_readers.in_window(ctx, "serve.step")
    if not d or not spans:
        return None
    held = [s[span_readers.ARGS]["state_slots"]
            for s in span_readers.named(spans, "serve.step")
            if "state_slots" in s[span_readers.ARGS]]
    if not held:
        return None
    return 100.0 * sum(held) / (len(held) * d["max_batch"])
