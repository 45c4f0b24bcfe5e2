"""Traffic generator: one general reader of the mixes in ``bench/traffic/``.

A mix is a JSON file of parameters.  Two kinds exist:

* ``closed``: ``clients`` callers, each with one request outstanding and
  no think time; when a request finishes, its caller sends the next one
  from a shared list.
* ``open``: requests due on a schedule whatever the server does, with
  inter-arrival gaps of a Gamma law of the given rate and coefficient of
  variation (``cv`` 1 is Poisson, above 1 bursty).

Lengths follow a lognormal law (``median``, ``sigma``) clipped to
``[min, max]``, taken at ``lengths`` quantiles of it: admission compiles
eager operations for each distinct prompt length, and set-up serves one
request of each.  Every seed gets the same multiset of lengths and gaps
(the laws' quantiles at ``(i + 0.5) / n``), so the work of a run does
not change with the seed.  A closed loop's seed shuffles their order; an
open loop sends one fixed schedule whatever the seed, since its
latencies follow the order of its bursts.  The seed draws the prompt
ids, uniform over the vocabulary; no two prompts of a run begin with the
same id, so no prompt shares a prefix with another.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# fixed sample of the unit Gamma law used for its quantiles (no scipy)
_GAMMA_SAMPLE = 200_000
_GAMMA_SAMPLE_SEED = 0
_SCHEDULE_SEED = 1      # the open loop's arrival and length order


@dataclasses.dataclass(frozen=True)
class Request:
    """One generated request: its prompt ids, its output budget, the time
    it is due (seconds after the window opens; ``None`` in a closed loop)
    and its per-request seed."""

    prompt: np.ndarray
    max_new_tokens: int
    due_s: Optional[float]
    seed: int


@dataclasses.dataclass(frozen=True)
class Traffic:
    """A generated mix: the window's requests in the order they are sent,
    the warm-up requests, and the parameters the server is sized by."""

    kind: str
    requests: List[Request]
    warmup: List[Request]
    clients: int
    slots: int
    max_prompt_len: int
    max_new_tokens: int


def load_mix(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec.get("kind") not in ("closed", "open"):
        raise ValueError(f"{path}: kind must be 'closed' or 'open'")
    return spec


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """``n`` integer lengths at the law's quantiles ``(i + 0.5) / n``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(median * np.exp(sigma * z))
    return np.clip(x, lo, hi).astype(np.int64)


def gamma_gaps(n: int, rate: float, cv: float) -> np.ndarray:
    """``n`` inter-arrival gaps (s) at the quantiles of a Gamma law with
    mean ``1 / rate`` and coefficient of variation ``cv``, rescaled so that
    they sum to exactly ``n / rate``."""
    shape = 1.0 / (cv * cv)
    sample = np.random.default_rng(_GAMMA_SAMPLE_SEED).gamma(
        shape, 1.0, _GAMMA_SAMPLE)
    g = np.quantile(sample, (np.arange(n) + 0.5) / n)
    return g * (n / rate) / g.sum()


def _lengths(spec: dict, n: int) -> np.ndarray:
    return lognormal_quantiles(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])


def _prompt(rng: np.random.Generator, n: int, vocab: int,
            first: int) -> np.ndarray:
    p = rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)
    p[0] = first
    return p


def warmup_requests(lengths, rng: np.random.Generator, vocab: int,
                    new_tokens: int, firsts) -> List[Request]:
    """One short request per distinct prompt length the window sends:
    admission runs eager operations whose shapes follow the prompt's
    length, so each length is compiled in set-up and none in the
    window."""
    return [Request(_prompt(rng, int(n), vocab, int(f)), new_tokens, None,
                    int(rng.integers(2**31)))
            for n, f in zip(sorted(set(int(x) for x in lengths)), firsts)]


def generate(spec: dict, seed: int, vocab: int, seconds: float, *,
             warmup_new_tokens: int) -> Traffic:
    """The mix ``spec`` for one run of ``seconds`` from ``seed``."""
    rng = np.random.default_rng(seed)
    # an open loop's tails follow the order of its bursts and lengths, so
    # every seed gets one fixed schedule; a closed loop's rate does not,
    # and the seed shuffles it
    order = rng
    if spec["kind"] == "open":
        order = np.random.default_rng(_SCHEDULE_SEED)
        arr = spec["arrivals"]
        n = max(1, int(math.floor(arr["rate_per_s"] * seconds)))
        gaps = order.permutation(gamma_gaps(n, arr["rate_per_s"], arr["cv"]))
        # the first request is due when the window opens, the last one
        # before it closes
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        clients = 0
    else:
        clients = int(spec["clients"])
        n = clients * int(spec["rounds"])
        due = [None] * n
    # every consecutive run of `lengths` requests holds each quantile once
    k = int(spec["lengths"])
    rounds = -(-n // k)
    pgrid = _lengths(spec["prompt"], k)
    ogrid = _lengths(spec["output"], k)
    plen = np.concatenate([order.permutation(pgrid)
                           for _ in range(rounds)])[:n]
    olen = np.concatenate([order.permutation(ogrid)
                           for _ in range(rounds)])[:n]
    # no two prompts of a run, warm-up included, share a first token: the
    # mixes share nothing, and the prefix cache matches single tokens
    n_warm = len(set(int(x) for x in plen))
    firsts = rng.choice(vocab, size=n + n_warm, replace=False)
    reqs = [Request(_prompt(rng, int(plen[i]), vocab, int(firsts[i])),
                    int(olen[i]), None if due[i] is None else float(due[i]),
                    int(rng.integers(2**31)))
            for i in range(n)]
    warm = warmup_requests(plen, rng, vocab, warmup_new_tokens, firsts[n:])
    return Traffic(kind=spec["kind"], requests=reqs, warmup=warm,
                   clients=clients, slots=int(spec["slots"]),
                   max_prompt_len=int(spec["max_prompt_len"]),
                   max_new_tokens=int(spec["max_new_tokens"]))
