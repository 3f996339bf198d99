"""What the readers of the decode engine's spans share: the program's
telemetry events of a traced run (``facts["spans"]``, Chrome trace events
with ``ts`` and ``dur`` in microseconds) cut to the measured window
(``facts["window"]``: its opening and closing on the same clock), a span's
descendants by time containment on its thread, and a weighted quantile.
``drivers/decode_open.py`` passes both facts; where a driver passes neither,
every reader built on this finds nothing."""

from __future__ import annotations

import bisect


def window(facts):
    """(open, close) in the spans' microseconds, or None."""
    w = facts.get("window")
    return tuple(w) if w and None not in w and w[1] > w[0] else None


def named(facts, name: str) -> list:
    """The complete ("X") events called ``name``, in time order."""
    return sorted((e for e in facts.get("spans") or ()
                   if e.get("ph") == "X" and e.get("name") == name),
                  key=lambda e: e["ts"])


def started_in(events, w) -> list:
    return [e for e in events if w[0] <= e["ts"] < w[1]]


def held_by(parent, children, starts=None) -> list:
    """Those of ``children`` (in time order, ``starts`` their ``ts``) that
    begin inside ``parent`` on its thread: its descendants of that name."""
    starts = [c["ts"] for c in children] if starts is None else starts
    lo = bisect.bisect_left(starts, parent["ts"])
    hi = bisect.bisect_right(starts, parent["ts"] + parent["dur"])
    return [c for c in children[lo:hi] if c.get("tid") == parent.get("tid")]


def request_quantile(facts, arg: str, q: float):
    """The q-th quantile of one argument of ``serve.request`` over the
    requests whose submission (the span's start) lies in the window; None
    where nothing carries it."""
    from benchmark import harness
    w = window(facts)
    if w is None:
        return None
    got = [e["args"][arg]
           for e in started_in(named(facts, "serve.request"), w)
           if arg in (e.get("args") or {})]
    return harness.quantile(got, q) if got else None


def weighted_quantile(values, weights, q: float):
    """The smallest value at or under which ``q`` of the weight lies; None
    where there is no weight."""
    total = float(sum(weights))
    if total <= 0:
        return None
    seen = 0.0
    for v, w in sorted(zip(values, weights)):
        seen += w
        if seen >= q * total:
            return v
    return max(values)
