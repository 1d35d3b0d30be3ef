"""Load generator: how late ``submit`` ran against each request's due
time, 95th percentile, ms (host clock).  A starved generator shows here,
not as a slow server."""

from bench.stats import percentile


def read(run):
    lags = [r.t_submit - r.due for r in run.recs if r.t_submit is not None]
    v = percentile(lags, 95)
    return None if v is None else v * 1e3
