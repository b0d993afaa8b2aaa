"""Idle share of the worst device over the traced window, in percent:
1 - (union of its ops' intervals) / window, from the profiler trace."""


def read(r):
    return r.trace.idle_pct()
