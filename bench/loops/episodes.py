"""Scale from zero: bursts of ``burst`` requests, each reaching an endpoint
with no replica. A replica comes up from the pool, serves its burst to the
end and is dropped; the next burst arrives when it is gone. Bursts start
until the window closes, and the last one is served to its end, so every
request that arrived in the window can be checked."""
from __future__ import annotations

from bench.lib.record import now
from bench.systems.common import Invocation


def run(cell, win, requests) -> None:
    while now() < win.deadline:
        burst = [next(requests) for _ in range(cell.mix["burst"])]
        arrival = now()
        invs = [Invocation("request", arrival) for _ in burst]
        win.invocations.extend(invs)
        try:
            with cell.rec.span("bench.from_pool"):
                engine = cell.bring_up()
            live = [cell.track(engine, inv, p) for inv, p in zip(invs, burst)]
            while live:
                for t in cell.step(engine, win, live):
                    live.remove(t)
        except Exception:
            for inv in invs:
                if inv.first is None:
                    inv.failed = True
            cell.failures.record("episode")
