"""The one traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and makes the requests of a run from ``--seed``.

Every seed gets the same work in another order.  Lengths are the
quantiles of the mix's distribution at ``(k + 1/2) / n``, and the gaps
between arrivals are the quantiles of the exponential distribution at the
mix's rate, so two seeds differ in which request comes when, not in how
much there is to do.  The seed draws an independent random permutation
of each of the three: the gaps are exchangeable exponentials, a Poisson
process held to ``n`` arrivals in the window, so short gaps and long
requests cluster by chance as they do in real traffic.  The seed also
draws the prompt tokens.

Kinds of mix:

* ``open_loop`` — requests due on the wall clock at ``rate_per_s``
  (Poisson gaps), ``rate_per_s * seconds`` of them inside the window,
  then as many again as load while the window's requests drain;
* ``plans``    — back-to-back plans of the engine, no arrivals.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass
class Request:
    index: int
    due_s: float            # wall-clock offset from the window's start
    prompt: np.ndarray      # (prompt_len,) int32
    max_new: int
    counted: bool           # due inside the window


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at (k + 1/2)/n, clipped
    to [min, max] and rounded up to a multiple of ``round_up``."""
    qs = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(q) for q in qs])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = np.clip(np.ceil(x), dist["min"], dist["max"])
    step = dist.get("round_up", 1)
    x = np.ceil(x / step) * step
    return x.astype(np.int64)


def _gaps(n: int, span_s: float) -> np.ndarray:
    """Exponential quantiles, scaled so the ``n`` gaps sum to ``span_s``."""
    qs = (np.arange(n) + 0.5) / n
    g = -np.log1p(-qs)
    return g * (span_s / g.sum())


def _requests(rng: np.random.Generator, vocab: int, first_index: int,
              dues: np.ndarray, plens: np.ndarray, outs: np.ndarray,
              counted: bool) -> List[Request]:
    return [Request(first_index + k, float(dues[k]),
                    rng.integers(0, vocab, int(plens[k]), dtype=np.int32),
                    int(outs[k]), counted)
            for k in range(len(dues))]


def open_loop(mix: Dict[str, Any], seed: int, seconds: float, vocab: int
              ) -> List[Request]:
    """The window's requests (``counted``), then as many again after it,
    each due at its wall-clock offset."""
    rng = np.random.default_rng(np.random.SeedSequence(seed % 2**64))
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    out = []
    for part in range(2):
        gaps = rng.permutation(_gaps(n, seconds))
        dues = part * seconds + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        plens = rng.permutation(quantiles(mix["prompt"], n))
        outs = rng.permutation(quantiles(mix["output"], n))
        out += _requests(rng, vocab, part * n, dues, plens, outs, part == 0)
    return out


def lengths(dist: Dict[str, Any]) -> List[int]:
    """Every length ``dist`` can draw."""
    return sorted(set(int(x) for x in quantiles(dist, 1 << 16)))
