"""The one traffic generator: a data file of parameters in, a loop out.

A mix under ``benchmark/traffic/<name>.json`` names a loop kind
(``loops/<kind>.py``: how requests fall due) and the distributions of its
shapes and gaps (``dists/<dist>.py``: the distribution's value at given
quantiles).  Nothing here draws a length or a gap at random: every
distribution is sampled at its n mid-quantiles ((i + 0.5) / n), and the
pairing and order of the shapes and the order of the gaps are fixed
permutations (the file's ``order_seed``), so every run of a cell offers
the SAME requests at the SAME instants.  ``--seed`` draws the token ids
(and, in the cell, the weights) and nothing else: on the chip, reordering
the same multisets by the seed moved ``ttft_ms_p90`` by 27 % from seed to
seed while two runs of one seed agreed within 3 % (my chip runs, PR 24),
so the order is part of the mix, not of the seed.

A new distribution or loop kind is a new file in its directory; a mix that
uses only what is there is data alone.
"""

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(directory, name):
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no benchmark/{directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"{directory}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantiles(spec, n, rate=None):
    """The n mid-quantiles of ``spec`` as a list of floats, clipped to the
    spec's ``min``/``max`` where it has them."""
    qs = [(i + 0.5) / n for i in range(n)]
    vals = _load("dists", spec["dist"]).at(spec, qs, rate)
    if "min" in spec:
        vals = [max(v, spec["min"]) for v in vals]
    if "max" in spec:
        vals = [min(v, spec["max"]) for v in vals]
    return vals


def shapes(mix, n):
    """(prompt lengths, output lengths, the fixed generator): n paired
    shapes in offer order.  Both multisets are the mid-quantiles; the
    pairing and the order are two permutations from ``order_seed``.  The
    generator is returned for whatever else the loop orders (gaps)."""
    fixed = np.random.default_rng(mix["order_seed"])
    lens = lambda spec: np.asarray(
        [int(round(v)) for v in quantiles(spec, n)], np.int64)
    return (lens(mix["prompt"])[fixed.permutation(n)],
            lens(mix["output"])[fixed.permutation(n)], fixed)


def token_ids(rng, length, vocab):
    return rng.integers(0, vocab, int(length), dtype=np.int32)


def loop(mix, seed, seconds, vocab):
    """The mix's loop for one run: ``loops/<mix["loop"]>.py``'s ``Loop``.
    ``serve_cell.drive`` says what a loop answers."""
    return _load("loops", mix["loop"]).Loop(mix, seed, seconds, vocab)
