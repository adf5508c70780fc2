"""Shared IO for the bench tools' --json artifacts.

One writer, used by flash_bench / rnn_bench / longcontext_bench (and
any future point-streaming tool): rewrite the artifact ATOMICALLY
(sibling tmp + os.replace) after every measured point, so a timeout
kill or crash at any instant leaves the last good snapshot on disk.
The payload's "complete" flag is the tool's own word on whether the
run finished.
"""

import json
import os


def make_flush(path, payload):
    """Returns flush(complete: bool) writing ``payload`` to ``path``."""

    def flush(complete):
        payload["complete"] = bool(complete)
        if not path:
            return
        tmp = path + ".flush"
        with open(tmp, "w") as f:
            f.write(json.dumps(payload) + "\n")
        os.replace(tmp, path)

    return flush
