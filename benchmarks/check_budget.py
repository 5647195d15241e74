"""The budget of the comparison that decides ``correct``: the order its
answers go in, the floor it never goes below, and when it starts no further
one. Standard library only.

A run of a cell has to end well inside the driver's limit whatever its seed
drew, so the drivers of the session and slate cells compare their sample in a
FIXED ORDER: the window's longest answered history, the longest later query,
then the rest by length, longest first. Once ``check_budget_s`` seconds of the
comparison are spent (compilations counted) no further answer is started,
but never before the traffic file's ``check_floor`` has been compared::

    "check_budget_s": 100,
    "check_floor": {"answers": 8, "first_queries": 2, "later_queries": 1,
                    "later_past": 8192}

``answers`` compared, ``first_queries`` of them first queries of a session,
``later_queries`` of them later queries over a history of more than
``later_past`` items, and ALWAYS the window's longest answered history (the
load generator puts it, and the longest later query, into every sample). A
file without the two keys has no budget, and the whole sample as its floor.
The check ``answers_compared`` holds a run to the floor's composition even
where the budget was never spent: a sample that lacks the long histories the
floor names fails.
"""

from __future__ import annotations

import time


def ordered(entries: list) -> list:
    """``entries`` (each with ``rows`` and ``first``) in the comparison's
    order."""
    rest = sorted(entries, key=lambda e: -len(e["rows"]))
    out = rest[:1]
    rest = rest[1:]
    later = next((e for e in rest if not e["first"]), None)
    if later is not None:
        rest.remove(later)
        out.append(later)
    return out + rest


def floor_of(mix: dict, answered: int) -> dict:
    """The traffic file's floor, its count held to what the window answered
    and to ``check_sample``."""
    floor = {"answers": int(mix["check_sample"]), "first_queries": 0,
             "later_queries": 0, "later_past": 0,
             **(mix.get("check_floor") or {})}
    floor["answers"] = min(int(floor["answers"]), int(mix["check_sample"]),
                           answered)
    return floor


def floor_met(done: list, floor: dict, window_longest: int) -> bool:
    """Whether the compared entries ``done`` hold what ``floor`` names."""
    return (len(done) >= floor["answers"] > 0
            and max(len(e["rows"]) for e in done) >= window_longest
            and sum(1 for e in done if e["first"]) >= floor["first_queries"]
            and sum(1 for e in done if not e["first"]
                    and len(e["rows"]) > floor["later_past"])
            >= floor["later_queries"])


class Budget:
    """One run's comparison: ``entries`` in order, ``stop`` for the
    reference's ``compare``, ``check`` for the result."""

    def __init__(self, mix: dict, entries: list, answered: int,
                 window_longest: int, clock=time.perf_counter):
        self.entries = ordered(entries)
        self.seconds = mix.get("check_budget_s")
        self.want = min(int(mix["check_sample"]), answered)
        self.floor = floor_of(mix, answered)
        self.window_longest = int(window_longest)
        self.clock, self.t0 = clock, clock()
        self.stopped_at = None

    def spent(self) -> float:
        return self.clock() - self.t0

    def stop(self, done: int) -> bool:
        """Asked before each answer: true where the budget is spent and the
        floor compared."""
        if (self.seconds is not None and self.spent() >= float(self.seconds)
                and floor_met(self.entries[:done], self.floor,
                              self.window_longest)):
            self.stopped_at = done
            return True
        return False

    def check(self, compared: int) -> dict:
        """``answers_compared``: all of the sample, or, where the budget
        stopped it, the floor; the floor's composition either way."""
        fired = self.stopped_at is not None
        need = self.floor["answers"] if fired else self.want
        ok = (compared >= need > 0 and floor_met(
            self.entries[:compared], self.floor, self.window_longest))
        return {"name": "answers_compared", "value": compared,
                "limit": f">= {need}", "ok": ok}

    def note(self, compared: int) -> str:
        floor = self.floor
        return (f"comparison budget: {compared} of {len(self.entries)} "
                f"sampled answers compared in {self.spent():.2f} s of "
                + ("no budget" if self.seconds is None
                   else f"{self.seconds} s")
                + (f"; the budget was spent after {self.stopped_at} and no "
                   "further answer was started" if self.stopped_at is not None
                   else "; the budget did not fire")
                + f"; floor {floor['answers']} answers, "
                f"{floor['first_queries']} first queries, "
                f"{floor['later_queries']} later past {floor['later_past']}, "
                f"the window's longest ({self.window_longest})")
