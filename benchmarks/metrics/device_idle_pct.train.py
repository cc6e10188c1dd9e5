"""Device: the share of a step's wall time in which the step program was not
running on the device, 1 - (the program's device time a step, from its events
in the traced slice) / (the mean interval between step completions over the
untraced part of the same run's window), in percent.  About 0 while the step
program is all there is to a step; it may read a few hundredths under 0,
which is the two clocks' disagreement.

Not read from the traced slice's own gaps: with the profiler on, the device
stalls with work queued every few steps (benchmarks/trace/reduce.py), which
the untraced loop does not.  The log line gives the slice's own reading with
those stalls taken out."""

from benchmarks.trace import reduce


def read(r):
    t = r["trace"]
    s = t["untraced"]
    stalls = t["queued_stalls"]
    r["log"](f"traced slice {t['slice_s']:.3f}s, of which {stalls['count']} stall(s) with a step "
             f"queued {stalls['seconds']:.3f}s (longest {stalls['longest_s'] * 1e3:.1f} ms) taken "
             f"out; in the rest the device idled {t['idle_share'] * 100:.3f} %, of which inside "
             f"running programs {t['in_program_idle_s'] / t['window_s'] * 100:.3f} %; longest gap "
             f"kept {t['longest_gap_s'] * 1e3:.3f} ms")
    if not s["steps"] or not s["seconds"]:
        return None
    _, program = reduce.step_program(t)
    step_s = program["seconds"] / program["count"]
    interval_s = s["seconds"] / s["steps"]
    r["log"](f"step program {step_s * 1e3:.4f} ms a run on the device; interval between "
             f"completions, untraced, {interval_s * 1e3:.4f} ms over {s['steps']} intervals")
    return (1.0 - step_s / interval_s) * 100.0
