"""Scheduler: from a request's due time to the start of the engine step in
which it got a slot, 95th percentile, ms (host clock).  A request still
without a slot when the drain after the window ended counts as
due-to-drain-end."""

from bench.stats import percentile


def read(run):
    end = run.window.t_drained
    waits = [(r.admitted_at if r.admitted_at is not None else end) - r.due
             for r in run.recs if not r.rejected]
    v = percentile(waits, 95)
    return None if v is None else v * 1e3
