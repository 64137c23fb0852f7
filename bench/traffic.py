"""The one traffic generator.  A mix is a data file,
``bench/traffic/<mix>.json``; this module turns it and a seed into the
requests of one run.

Keys of a mix:

- ``loop``: ``"open"`` (requests due on a schedule, however the system
  keeps up) or ``"closed"`` (``clients`` callers, each sending its next
  request when the last one resolved);
- ``prompt_len``, ``endpoint_batch``: the token rows each request carries
  and the batch every endpoint is compiled for;
- ``entries``: the endpoints requests enter at, in order of popularity;
- ``rate_per_s`` (open loop): arrivals per second over all entries,
  spread over them as Zipf with exponent ``zipf_s`` (0, or left out:
  evenly);
- ``arrivals_seed`` (open loop, optional): draw the arrival times and
  entries from this seed instead of the run's, so that every run gets the
  same schedule; the run's seed still draws the prompts;
- ``chains``: lists of endpoints a request passes through in turn, the
  client sending stage k + 1 when stage k resolves, as a trigger would;
- ``keep_alive_s``, ``max_instances``, ``batch_max_wait_s``: the pool and
  batcher settings each endpoint is deployed with;
- ``idle_before_window``: wait out the keep-alive between set-up and the
  window, so that every endpoint starts the window idle;
- ``check``: how many served requests the correctness check compares
  (``"all"`` or a number drawn from the seed), ``check_from_first`` for a
  closed loop: the sample is drawn from that many first requests;
- ``trace_seconds``: how much of the window a traced run records (null:
  all of it).

Every seed gets the same amount of work: in an open loop each entry
endpoint's inter-arrival gaps are the same set of exponential quantiles,
in an order drawn from the seed, so the number of arrivals and of gaps
longer than the keep-alive do not depend on the seed; only the order and
the interleaving do.  Where the interleaving is itself the work, as where
whether an arrival finds its endpoint warm depends on how long the
previous request held it, a mix fixes ``arrivals_seed``.  Prompts are
uniform token ids drawn from the seed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import numpy as np

def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def endpoints(mix: dict) -> List[str]:
    """Every endpoint of the mix, entries first, then chain stages."""
    names = list(mix["entries"])
    for chain in mix.get("chains", []):
        names += [n for n in chain if n not in names]
    return names


def successors(mix: dict) -> Dict[str, str]:
    nxt = {}
    for chain in mix.get("chains", []):
        nxt.update(zip(chain, chain[1:]))
    return nxt


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def entry_weights(mix: dict) -> np.ndarray:
    s = float(mix.get("zipf_s", 0.0))
    w = 1.0 / np.arange(1, len(mix["entries"]) + 1) ** s
    return w / w.sum()


@dataclass
class Schedule:
    due: np.ndarray          # seconds after the window opens, ascending
    entry: np.ndarray        # index into ``endpoints(mix)``
    after_idle: np.ndarray   # the entry had no arrival for > keep-alive
    prompts: np.ndarray      # (n, prompt_len) int32

    def __len__(self):
        return len(self.due)


def _arrivals(rate: float, seconds: float,
              g: np.random.Generator) -> np.ndarray:
    """One entry's arrival times at ``rate`` per second: the same
    exponential-quantile gaps for every seed, permuted, after an offset
    drawn from the seed."""
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    mean = seconds / n
    gaps = -np.log1p(-(np.arange(n - 1) + 0.5) / max(1, n - 1))
    gaps = g.permutation(gaps) * (mean * (n - 1) / max(gaps.sum(), 1e-12))
    return g.uniform(0.0, mean) + np.concatenate([[0.0], np.cumsum(gaps)])


def open_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                  rate_scale: float = 1.0) -> Schedule:
    g = rng(mix.get("arrivals_seed", seed), 1)
    keep = float(mix["keep_alive_s"])
    rate = float(mix["rate_per_s"]) * rate_scale
    due, entry, idle = [], [], []
    for i, w in enumerate(entry_weights(mix)):
        t = _arrivals(rate * w, seconds, g)
        due.append(t)
        entry.append(np.full(len(t), i))
        first_idle = bool(mix.get("idle_before_window"))
        idle.append(np.concatenate([[first_idle], np.diff(t) > keep])[:len(t)])
    due, entry, idle = (np.concatenate(x) for x in (due, entry, idle))
    order = np.argsort(due, kind="stable")
    prompts = rng(seed, 2).integers(0, vocab, (len(due), mix["prompt_len"]),
                                    dtype=np.int32)
    return Schedule(due[order], entry[order], idle[order].astype(bool),
                    prompts)


def closed_prompts(mix: dict, seed: int, vocab: int) -> np.ndarray:
    return rng(seed, 2).integers(0, vocab, (mix["prompt_pool"],
                                            mix["prompt_len"]), dtype=np.int32)


def check_sample(mix: dict, seed: int, n: int) -> np.ndarray:
    """Indices of the requests the correctness check compares."""
    want = mix.get("check", "all")
    if want == "all" or want >= n:
        return np.arange(n)
    return np.sort(rng(seed, 3).choice(n, size=int(want), replace=False))
