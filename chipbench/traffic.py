"""The one general traffic generator: a mix is a data file of parameters
(``traffic/<name>.json``) whose ``generator`` names a function as
``module:function`` under ``chipbench`` (those below are ``traffic:<name>``;
a later PR adds a generator as a new module and edits nothing here).

Serving mixes are a FIXED multiset of (prompt length, output length) pairs,
written out by the file's parameters alone: the stratified quantiles of two
clipped log-normals, paired by a permutation from the FILE's pairing seed,
the prompt cut where ``max_total`` (the longest context the engine holds)
says so.
The due times of an open-loop mix are a FIXED set too: one draw of a Poisson
process given its count, from the file's arrival seed; there the run's seed
decides the token ids and which request arrives at which due time.  A backlog
is cut off by the window, so the order decides which requests the window
holds: its order is the FILE's too (``order_seed``), and the run's seed
decides the token ids (and, in the builder, the weights).  Every seed and
every run then does the same work; load that differs from seed to seed would
change it (by the sizes alone the tokens a 44 s window emits move by 1.2 %
with the order: ``PERF.md`` section 6).
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, List, Mapping, Tuple

import numpy as np


@dataclasses.dataclass
class Request:
    rid: str
    prompt: np.ndarray      # int32 token ids
    new_tokens: int
    due_s: float            # seconds after the window opens


def _lognormal_quantiles(spec: Mapping[str, float], n: int) -> np.ndarray:
    """n stratified quantiles of a log-normal, clipped and rounded."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    values = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(values), spec["min"], spec["max"]).astype(np.int64)


def size_multiset(traffic: Mapping[str, Any], n: int) -> List[Tuple[int, int]]:
    """The n (prompt_len, new_tokens) pairs: a function of the file and n only."""
    prompts = _lognormal_quantiles(traffic["prompt_len"], n)
    outputs = _lognormal_quantiles(traffic["new_tokens"], n)
    outputs = outputs[np.random.default_rng(traffic["pairing_seed"]).permutation(n)]
    if "max_total" in traffic:      # the context may not pass what the engine holds
        prompts = np.minimum(prompts, traffic["max_total"] - outputs)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def offered_count(traffic: Mapping[str, Any], seconds: float) -> Tuple[int, float]:
    """(N, arrival span) of an open-loop run of ``seconds``.  At the length
    the file was written for, N is the file's ``requests``; a shorter trial
    run keeps the rate and offers fewer."""
    span = min(float(traffic["arrival_span_s"]), seconds - float(traffic["drain_s"]))
    if span <= 0:
        raise ValueError(
            f"--seconds {seconds} leaves no arrival span before drain_s "
            f"{traffic['drain_s']}")
    rate = traffic["requests"] / float(traffic["arrival_span_s"])
    return max(1, int(round(rate * span))), span


def _requests(sizes: List[Tuple[int, int]], dues: np.ndarray, vocab: int,
              rng: np.random.Generator) -> List[Request]:
    return [
        Request(f"q{i}", rng.integers(0, vocab, size=p, dtype=np.int32), o, float(due))
        for i, ((p, o), due) in enumerate(zip(sizes, dues))
    ]


def open_loop_fixed_set(traffic: Mapping[str, Any], seed: int, seconds: float,
                        vocab: int) -> List[Request]:
    """N requests of the fixed multiset in seeded order, due at the file's own
    N sorted uniform draws over the arrival span (a Poisson process given its
    count; the same realization for every seed)."""
    rng = np.random.default_rng(seed)
    n, span = offered_count(traffic, seconds)
    sizes = size_multiset(traffic, n)
    order = rng.permutation(n)
    dues = np.sort(np.random.default_rng(traffic["arrival_seed"]).uniform(0.0, span, size=n))
    return _requests([sizes[i] for i in order], dues, vocab, rng)


def backlog(traffic: Mapping[str, Any], seed: int, seconds: float,
            vocab: int) -> List[Request]:
    """The file's multiset, ``cycles`` times over, each cycle in an order of
    its own from the file's ``order_seed``, all due at 0; token ids from the
    run's seed."""
    del seconds
    sizes = size_multiset(traffic, traffic["requests"])
    order = np.random.default_rng(traffic["order_seed"])
    ordered = [sizes[i] for _ in range(traffic["cycles"])
               for i in order.permutation(len(sizes))]
    return _requests(ordered, np.zeros(len(ordered)), vocab, np.random.default_rng(seed))


def train_fixed(traffic: Mapping[str, Any], seed: int, rows: int, seq: int,
                vocab: int) -> np.ndarray:
    """``distinct_batches`` batches of token ids [k, rows, seq + 1]; the
    window cycles through them.  All rows differ."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(traffic["distinct_batches"], rows, seq + 1),
                        dtype=np.int32)
