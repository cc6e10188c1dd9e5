"""Train loop, host side: host clock around ``next(dev_iter)`` (device_put of
the next batch, the prefetch queue) minus the feeder wait inside it, mean per
step over the untraced part of the traced run's window."""


def read(r):
    s = r["trace"]["untraced"]
    if not s["steps"]:
        return None
    return max(0.0, s["h2d_s"] - s["feeder_wait_s"]) / s["steps"] * 1e3
