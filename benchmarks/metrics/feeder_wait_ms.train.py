"""Input pipeline: host clock around ``next()`` of the host iterator inside
the loop, mean per step over the untraced part of the traced run's window (StepTimeline's wait_data)."""


def read(r):
    s = r["trace"]["untraced"]
    return s["feeder_wait_s"] / s["feeder_calls"] * 1e3 if s["feeder_calls"] else None
