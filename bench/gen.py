"""Traffic generation from a mix's parameters and a seed.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in an order of its own: lengths and gaps are the distribution's quantiles
at (i + 1/2) / n, and the seed shuffles them and draws the token ids.  So
the work of a run is fixed by the mix and its length, and the seed changes
only which request comes when.

A mix names the distribution of each of its sizes and of its arrivals by
``dist``; ``bench/dists/<dist>.py`` gives its quantiles, so a mix with a
new distribution is new files and no edit here.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness import BENCH, load_module, rng


def midpoints(spec: dict, n: int) -> np.ndarray:
    """n values of the distribution ``spec`` at the midpoints of n equal
    slices of probability."""
    path = BENCH / "dists" / f"{spec['dist']}.py"
    if not path.is_file():
        raise ValueError(f"unknown distribution {spec['dist']!r}: no {path}")
    dist = load_module(path, f"bench_dist_{spec['dist']}")
    return np.asarray(dist.quantile(spec, (np.arange(n) + 0.5) / n), float)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``midpoints`` as integers clipped to [min, max]: lengths."""
    x = np.rint(midpoints(spec, n))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int
                   ) -> list[Request]:
    """The open-loop arrivals of one window: ``rate_per_s * seconds``
    requests, all due inside the window, with the gaps of the mix's
    ``arrivals`` scaled so the last is due before the window closes."""
    arrivals = mix["arrivals"]
    n = max(1, int(round(arrivals["rate_per_s"] * seconds)))
    g = rng(seed, 7)
    prompts = g.permutation(quantiles(mix["prompt"], n))
    outputs = g.permutation(quantiles(mix["output"], n))
    gaps = g.permutation(midpoints(arrivals, n))
    due = np.cumsum(gaps) - gaps[0]
    if due[-1] > 0:
        due *= seconds * (1.0 - 0.5 / n) / due[-1]
    return [Request(float(d), g.integers(0, vocab, size=int(p),
                                         dtype=np.int32), int(o))
            for d, p, o in zip(due, prompts, outputs)]


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int
                ) -> dict:
    """Token ids of one step, uniform over the vocabulary (a slice of it
    for a vocabulary-split share); labels are the next tokens."""
    g = rng(seed, 11, step)
    stream = g.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}
