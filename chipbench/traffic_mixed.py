"""Generator ``traffic_mixed:backlog_mixed``: a backlog whose fixed multiset
of (prompt length, output length) pairs is drawn from SEVERAL classes of
request in one queue (``traffic/<name>.json`` ``classes``: each a share of the
``requests``, a prompt law and an output law), where ``traffic:backlog`` has
one law for all.

Each class is written out as ``traffic:backlog`` writes its one: the
stratified quantiles of its two clipped log-normals, paired by a permutation
from the FILE's pairing seed (``traffic.size_multiset``).  The file's one
``max_total`` (the longest context the pool holds) cuts a prompt of any class.
The classes' pairs, class after class, are the cycle's multiset; ``cycles``
times over, each cycle in an order of its own from the file's ``order_seed``,
all due at 0; token ids from the run's seed.  Every seed and every run then
does the same work.
"""

from __future__ import annotations

from typing import Any, List, Mapping, Tuple

import numpy as np

from chipbench.traffic import Request, _requests, size_multiset


def class_counts(traffic: Mapping[str, Any]) -> List[int]:
    """Requests of each class in one cycle: its share of ``requests``,
    which has to come out whole."""
    counts = [traffic["requests"] * c["share"] for c in traffic["classes"]]
    if any(abs(n - round(n)) > 1e-9 for n in counts) or round(sum(counts)) != traffic["requests"]:
        raise ValueError(f"shares {[c['share'] for c in traffic['classes']]} do not divide "
                         f"{traffic['requests']} requests into whole classes")
    return [int(round(n)) for n in counts]


def class_multisets(traffic: Mapping[str, Any]) -> List[List[Tuple[int, int]]]:
    """Each class's (prompt_len, new_tokens) pairs: a function of the file only."""
    return [
        size_multiset(dict(c, pairing_seed=traffic["pairing_seed"],
                           max_total=traffic["max_total"]), n)
        for c, n in zip(traffic["classes"], class_counts(traffic))
    ]


def clipped(traffic: Mapping[str, Any]) -> List[int]:
    """How many requests of each class the file's one clip touches (the
    prompt cut so that prompt + output fits ``max_total``)."""
    out = []
    for c, n in zip(traffic["classes"], class_counts(traffic)):
        free = size_multiset(dict(c, pairing_seed=traffic["pairing_seed"]), n)
        cut = size_multiset(dict(c, pairing_seed=traffic["pairing_seed"],
                                 max_total=traffic["max_total"]), n)
        out.append(sum(a != b for a, b in zip(free, cut)))
    return out


def backlog_mixed(traffic: Mapping[str, Any], seed: int, seconds: float,
                  vocab: int) -> List[Request]:
    del seconds
    sizes = [pair for pairs in class_multisets(traffic) for pair in pairs]
    order = np.random.default_rng(traffic["order_seed"])
    ordered = [sizes[i] for _ in range(traffic["cycles"])
               for i in order.permutation(len(sizes))]
    return _requests(ordered, np.zeros(len(ordered)), vocab, np.random.default_rng(seed))
