"""Traffic generation: one general generator over the mixes in ``traffic/``.

A mix is a JSON file of parameters. Every seed gets the same multiset of
prompt lengths, output lengths and inter-arrival gaps; the seed only
chooses their order and the token ids. That keeps the work of a run
fixed, so runs with different seeds spread no more than runs of one seed.

* Lengths are the quantiles ``(i + 0.5) / n`` of a lognormal (median,
  sigma), rounded and clipped to ``[min, max]``. They are dealt in van der
  Corput order, rotated by a seeded offset, so every prefix of the
  sequence (what a window serves of a backlog) is a balanced sample.
* ``poisson`` arrivals: ``n = floor(rate * seconds)`` gaps at the
  quantiles of the exponential, in a seeded random order, scaled so the
  last request is due just before the window closes. Exactly ``n``
  requests are due in every window. With ``block: b`` the order is
  stratified: every ``b`` consecutive gaps take one gap from each of the
  ``b`` strata of the sorted gaps, so short and long gaps are as near as
  any seed gets them, and a burst lasts a block at most.
* ``backlog`` arrivals: ``requests`` requests, all due when the window
  opens.
* After the window, ``follow_on`` continues open-loop arrivals (its own
  draw, not counted) while the harness waits for the first tokens of the
  window's last requests.

The schedule is the copy kept here of ``launch/serve.py::arrival_offsets``
(Poisson gaps); its bursts and ``mixed_requests``' uniform lengths are not
copied.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of a schedule: what a client sends, and when."""

    index: int
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int
    temperature: float
    top_k: int
    greedy: bool               # every token greedy (checked for correctness)
    seed: int                  # the request's own sampling seed


def load_mix(name: str, root: pathlib.Path = TRAFFIC_DIR) -> dict:
    """The parameters of mix ``name`` (``traffic/<name>.json``)."""
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """The run's generator (``stream`` 0: the window's requests; 1: the
    load that follows it); any whole number, of any size, is a seed."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return np.random.default_rng(seed if stream == 0 else [seed, stream])


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """``n`` stratified lognormal lengths, ascending, clipped to [lo, hi]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def vdc_order(n: int) -> np.ndarray:
    """Indices ``0..n-1`` in van der Corput (bit-reversed) order."""
    bits = max(1, (n - 1).bit_length())
    rev = [int(f"{i:0{bits}b}"[::-1], 2) for i in range(n)]
    return np.argsort(np.asarray(rev), kind="stable")


def dealt(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` in van der Corput order, rotated by a seeded offset."""
    order = vdc_order(len(values))
    return values[np.roll(order, -int(rng.integers(len(values))))]


def stratified_order(n: int, block: int,
                     rng: np.random.Generator) -> np.ndarray:
    """A seeded order of ranks ``0..n-1`` in which every ``block``
    consecutive positions hold one rank from each of ``block`` strata of
    consecutive ranks (the last block may miss some strata)."""
    strata = np.array_split(np.arange(n), block)
    blocks = [[] for _ in range(math.ceil(n / block))]
    for stratum in strata:
        for j, rank in enumerate(rng.permutation(stratum)):
            blocks[j].append(int(rank))
    return np.concatenate([rng.permutation(b) for b in blocks if b])


def exponential_gaps(n: int, rate: float, seconds: float,
                     rng: np.random.Generator,
                     block: int | None = None) -> np.ndarray:
    """``n`` inter-arrival gaps at the exponential's quantiles, shuffled
    in blocks of ``block`` (one block of all ``n`` where not given),
    summing to ``seconds * (1 - 0.5 / n)``."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps = gaps[stratified_order(n, block or n, rng)]
    return gaps * (seconds * (1.0 - 0.5 / n) / gaps.sum())


def schedule(mix: dict, vocab: int, seed: int, seconds: float,
             stream: int = 0) -> list[Item]:
    """The requests of one run of ``mix``, in due order."""
    rng = rng_for(seed, stream)
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        n = max(1, math.floor(arr["rate_per_s"] * seconds))
        due = np.cumsum(exponential_gaps(n, arr["rate_per_s"], seconds, rng,
                                         arr.get("block")))
    elif arr["process"] == "backlog":
        n = int(arr["requests"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    pl, ol = mix["prompt_tokens"], mix["output_tokens"]
    prompts = dealt(lognormal_lengths(n, pl["median"], pl["sigma"],
                                      pl["min"], pl["max"]), rng)
    outputs = dealt(lognormal_lengths(n, ol["median"], ol["sigma"],
                                      ol["min"], ol["max"]), rng)
    smp = mix["sampling"]
    items = []
    for i in range(n):
        items.append(Item(
            index=i, due=float(due[i]),
            prompt=rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
            max_new=int(outputs[i]),
            temperature=float(smp["temperature"]), top_k=int(smp["top_k"]),
            greedy=(smp["temperature"] == 0.0
                    or i % int(smp["greedy_every"]) == 0),
            seed=int(rng.integers(2 ** 31))))
    return items


def follow_on(mix: dict, vocab: int, seed: int, seconds: float,
              span: float, first_index: int) -> list[Item]:
    """Open-loop arrivals go on after the window closes, for ``span``
    seconds: a draw of its own (stream 1) of the same mix, due from
    ``seconds`` on and indexed from ``first_index``. They are served, not
    counted: they keep the load on while the window's last requests wait
    for their first tokens. A backlog has none."""
    if mix["arrivals"]["process"] != "poisson":
        return []
    return [dataclasses.replace(it, index=first_index + it.index,
                                due=seconds + it.due)
            for it in schedule(mix, vocab, seed, span, stream=1)]
