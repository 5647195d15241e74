"""The browsing sessions a ``session_queries`` cell plays: standard library
only, so that the load generator (a process without JAX) and the driver make
the very same list from the mix's parameters.

A session is ``queries_per_session`` queries. The first carries a history of
``H`` items; each later one carries the history so far plus 1..``grow_max``
new items (uniform). ``H`` takes the ``history_quantiles`` quantile mid-points
of a lognormal (``history_median``, ``history_sigma``), clipped to
``[history_min, history_max]``; every connection plays them in one fixed
shuffled order of its own, over and over. A session has a topic: an item
comes from the session's own topic with probability ``own_topic``, else from
any topic, and inside a topic items follow a Zipf law (``zipf_exponent``)
over the topic's rows of the catalogue. Everything follows ``sessions_seed``:
the list is the same for every ``--seed`` of the run.
"""

from __future__ import annotations

import bisect
import itertools
import math
import random
from statistics import NormalDist


def history_lengths(mix: dict) -> list:
    """The ``history_quantiles`` quantile mid-points of the lognormal."""
    q = int(mix["history_quantiles"])
    normal = NormalDist()
    out = []
    for i in range(q):
        h = float(mix["history_median"]) * math.exp(
            float(mix["history_sigma"]) * normal.inv_cdf((i + 0.5) / q))
        out.append(int(min(max(round(h), int(mix["history_min"])),
                           int(mix["history_max"]))))
    return out


class Sessions:
    """``session(connection, index)`` -> the item rows of each of its
    queries, ``[[rows of query 0], [rows of query 1], ...]``."""

    def __init__(self, mix: dict, n_items: int):
        self.mix, self.n_items = mix, int(n_items)
        self.seed = int(mix["sessions_seed"])
        self.topics = int(mix["topics"])
        self.per_topic = self.n_items // self.topics
        weights = [(r + 1) ** -float(mix["zipf_exponent"])
                   for r in range(self.per_topic)]
        self.cum = list(itertools.accumulate(weights))
        self.lengths = history_lengths(mix)

    def order(self, connection: int) -> list:
        lengths = list(self.lengths)
        random.Random(f"{self.seed}/order/{connection}").shuffle(lengths)
        return lengths

    def _items(self, rng: random.Random, topic: int, n: int) -> list:
        own, topics, per = float(self.mix["own_topic"]), self.topics, \
            self.per_topic
        ranks = [bisect.bisect_left(self.cum, u * self.cum[-1])
                 for u in (rng.random() for _ in range(n))]
        return [(topic if rng.random() < own else rng.randrange(topics))
                * per + min(r, per - 1) for r in ranks]

    def session(self, connection: int, index: int) -> list:
        order = self.order(connection)
        rng = random.Random(f"{self.seed}/session/{connection}/{index}")
        topic = rng.randrange(self.topics)
        history = self._items(rng, topic, order[index % len(order)])
        queries = [list(history)]
        for _ in range(int(self.mix["queries_per_session"]) - 1):
            history = history + self._items(
                rng, topic, rng.randint(1, int(self.mix["grow_max"])))
            queries.append(history)
        return queries
