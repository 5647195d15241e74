"""The sessions a ``slate_queries`` cell plays: ``session_traffic``'s list
(history lengths from a lognormal's quantile mid-points, Zipf items inside a
session's topic, one fixed order per connection, everything from
``sessions_seed``) with the growth between queries given as a range: each
later query carries the history so far plus ``grow_min``..``grow_max`` new
items (uniform) — what the user took since the last slate, drawn from the
session's topic, never from the slate. Standard library only, as its base.
"""

from __future__ import annotations

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import session_traffic  # noqa: E402

history_lengths = session_traffic.history_lengths


class Sessions(session_traffic.Sessions):
    def session(self, connection: int, index: int) -> list:
        order = self.order(connection)
        rng = random.Random(f"{self.seed}/session/{connection}/{index}")
        topic = rng.randrange(self.topics)
        history = self._items(rng, topic, order[index % len(order)])
        queries = [list(history)]
        for _ in range(int(self.mix["queries_per_session"]) - 1):
            history = history + self._items(rng, topic, rng.randint(
                int(self.mix["grow_min"]), int(self.mix["grow_max"])))
            queries.append(history)
        return queries
