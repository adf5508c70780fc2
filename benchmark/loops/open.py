"""Open loop: requests fall due on a fixed schedule whatever the engine
does.  The schedule holds ``rate`` x (``ramp_s`` + window) requests; the
window opens ``ramp_s`` after the schedule starts."""

import numpy as np

import traffic


class Loop:
    def __init__(self, mix, seed, seconds, vocab):
        self.mix = mix
        n = int(round(mix["rate"] * (mix["ramp_s"] + seconds)))
        self.prompt_len, self.output_len, fixed = traffic.shapes(mix, n)
        gaps = np.asarray(traffic.quantiles(mix["gaps"], n, mix["rate"]))
        self.due = np.cumsum(gaps[fixed.permutation(n)])   # from the start
        rng = np.random.default_rng(int(seed))
        self.prompts = [traffic.token_ids(rng, p, vocab)
                        for p in self.prompt_len]
        self.sent = 0

    def start(self, t0):
        self.sent = 0
        self.at = t0 + self.due
        self.opens = t0 + self.mix["ramp_s"]

    def window_opens(self, now, finished):
        return now >= self.opens

    def take(self, now):
        """The requests to submit now: (token ids, new tokens, due)."""
        out = []
        while self.sent < len(self.at) and self.at[self.sent] <= now:
            i = self.sent
            out.append((self.prompts[i], int(self.output_len[i]),
                        float(self.at[i])))
            self.sent += 1
        return out

    def on_finish(self, rec, now):
        pass

    def next_due(self):
        """When the next request falls due (None: none is left)."""
        return float(self.at[self.sent]) if self.sent < len(self.at) else None
