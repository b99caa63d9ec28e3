"""How late the generator sent: 95th percentile of (sent - due) over the
requests of the window, host clock.  A starved generator must not be read as
a fast server."""

from benchmarks import stats


def read(view):
    lag = view["counters"].get("loadgen_lag_ms")
    return stats.percentile(lag, 95) if lag else None
