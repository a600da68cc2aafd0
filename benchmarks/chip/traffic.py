"""The one traffic generator: it reads a mix's parameters from its data
file under ``traffic/`` and makes every request of a run from ``--seed``.

A mix file is JSON.  ``"base": "<name>"`` pulls in another mix's keys,
which the file's own keys override, so a cell at another rate is one
small data file.  Serving mixes (``"kind": "serve"``) give:

* ``prompt_menu`` and ``prompt_lognormal`` ``{median, sigma}``: prompt
  lengths come from the lognormal, binned onto the menu (each menu value
  takes the mass between the geometric midpoints to its neighbours);
* ``output_lognormal`` ``{median, sigma, min, max}``: output lengths,
  rounded and clipped;
* ``rate_per_s`` and ``arrival_seed``: open-loop arrivals,
  ``round(rate * seconds)`` of them spread as uniform order statistics
  over the window (a Poisson process given its count), and as many again
  over ``tail_s`` after it, so that the last requests of the window still
  see the same load.  The times are one draw, from ``arrival_seed``, the
  same for every run;
* ``size_bands``: each run of that many consecutive arrivals takes one
  prompt length and one output length from each of as many bands of
  their quantiles (1: a plain shuffle);
* ``follow``: ``"first_token"`` runs past the window until every request
  due in it has its first token (for at most ``first_token_cap_s``),
  ``"window"`` ends the run when the window closes.

Every seed gets the same multiset of sizes and the same arrival times, in
another order: the sizes are the stratified quantiles of the length
distributions, put in a banded order by the seed, and the times are the
mix's own.  So the seed changes which request arrives when, and its
tokens, not how much work a run holds nor how it is spread over the
window.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load(name: str, directory: pathlib.Path = TRAFFIC_DIR) -> Dict:
    """The mix ``name`` with its ``base`` chain merged in."""
    path = directory / f"{name}.json"
    mix = json.loads(path.read_text())
    base = mix.pop("base", None)
    if base is None:
        return mix
    merged = load(base, directory)
    merged.update(mix)
    return merged


def arrival_times(n: int, span: float, rng) -> np.ndarray:
    """``n`` arrival times over ``[0, span)``: sorted uniform draws (a
    Poisson process given its count, as the uniform case of
    ``repro.fleet.scenarios.request_arrivals``)."""
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    if span <= 0 and n:
        raise ValueError(f"span must be positive, got {span}")
    return np.sort(rng.uniform(0.0, span, n))


def balanced_order(n: int, bands: int, rng) -> np.ndarray:
    """A seeded order of the quantile indices ``0..n-1`` in which every
    run of ``bands`` consecutive requests holds one index from each of
    ``bands`` equal bands of quantiles.  So each stretch of arrivals gets
    the same mix of short and long requests whatever the seed, and the
    seed changes which member of a band comes, and in what order."""
    idx = np.arange(n)
    members = [rng.permutation(idx[idx * bands // n == b])
               for b in range(bands)]
    out: List[int] = []
    for t in range(max((len(m) for m in members), default=0)):
        block = [int(m[t]) for m in members if t < len(m)]
        out += [block[j] for j in rng.permutation(len(block))]
    return np.asarray(out, dtype=np.int64)


def menu_weights(menu: List[int], median: float, sigma: float) -> List[float]:
    """Probability of each menu value under the binned lognormal."""
    nd = statistics.NormalDist()
    edges = [math.sqrt(a * b) for a, b in zip(menu, menu[1:])]
    cdf = [nd.cdf((math.log(e) - math.log(median)) / sigma) for e in edges]
    cdf = [0.0] + cdf + [1.0]
    return [hi - lo for lo, hi in zip(cdf, cdf[1:])]


def prompt_lengths(mix: Dict, n: int) -> List[int]:
    """The ``n`` prompt lengths every seed shares, in quantile order."""
    menu = list(mix["prompt_menu"])
    ln = mix["prompt_lognormal"]
    nd = statistics.NormalDist()
    edges = [math.sqrt(a * b) for a, b in zip(menu, menu[1:])]
    out = []
    for i in range(n):
        x = math.exp(math.log(ln["median"])
                     + ln["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(menu[sum(x > e for e in edges)])
    return out


def output_lengths(mix: Dict, n: int) -> List[int]:
    """The ``n`` output lengths every seed shares, in quantile order."""
    ln = mix["output_lognormal"]
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        x = math.exp(math.log(ln["median"])
                     + ln["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(ln["max"], max(ln["min"], round(x)))))
    return out


def max_len(mix: Dict) -> int:
    """Longest sequence a request of the mix can reach."""
    return max(mix["prompt_menu"]) + mix["output_lognormal"]["max"]


@dataclasses.dataclass
class Request:
    rid: int
    t_submit: float          # seconds after the window opens
    prompt: np.ndarray       # int32 token ids
    max_new: int             # output tokens, the prefill's first included
    in_window: bool


def serve_requests(mix: Dict, vocab: int, seed: int,
                   seconds: float) -> List[Request]:
    """Every request of one run, sorted by due time."""
    reqs: List[Request] = []
    rng = np.random.default_rng(seed)
    segments = ((0.0, float(seconds), True),
                (float(seconds), float(mix.get("tail_s", 0.0)), False))
    for k, (start, span, in_window) in enumerate(segments):
        n = int(round(mix["rate_per_s"] * span))
        if n == 0:
            continue
        times = arrival_times(
            n, span, np.random.default_rng([mix["arrival_seed"], k]))
        bands = min(n, int(mix.get("size_bands", 1)))
        plens = np.asarray(prompt_lengths(mix, n))[
            balanced_order(n, bands, rng)]
        olens = np.asarray(output_lengths(mix, n))[
            balanced_order(n, bands, rng)]
        for t, p, o in zip(times, plens, olens):
            reqs.append(Request(
                rid=len(reqs), t_submit=float(start + t),
                prompt=rng.integers(0, vocab, int(p), dtype=np.int32),
                max_new=int(o), in_window=in_window))
    return reqs
