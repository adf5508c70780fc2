"""Closed loop: ``clients`` clients, each sending its next request the
instant its last one finished.  ``shapes`` paired shapes are cycled in
their fixed order; the window opens when ``ramp_finished`` requests have
finished."""

import numpy as np

import traffic


class Loop:
    def __init__(self, mix, seed, seconds, vocab):
        self.mix, self.seed, self.vocab = mix, int(seed), vocab
        self.prompt_len, self.output_len, _ = traffic.shapes(
            mix, int(mix["shapes"]))

    def start(self, t0):
        self.sent = 0
        self.rng = np.random.default_rng(self.seed)
        self.pending = [t0] * self.mix["clients"]

    def window_opens(self, now, finished):
        return finished >= self.mix["ramp_finished"]

    def take(self, now):
        """The requests to submit now: (token ids, new tokens, due).  Every
        request has fresh token ids: nothing is shared with an earlier
        one."""
        out = []
        for due in self.pending:
            i = self.sent % len(self.prompt_len)
            out.append((traffic.token_ids(self.rng, self.prompt_len[i],
                                          self.vocab),
                        int(self.output_len[i]), due))
            self.sent += 1
        self.pending = []
        return out

    def on_finish(self, rec, now):
        self.pending.append(now)

    def next_due(self):
        return None           # an idle engine here means the loop broke
