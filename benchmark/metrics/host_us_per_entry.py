"""Host time per committed entry inside submit_pipelined: the benchmark's
span around each call, less the device's busy time inside it."""

from benchmark import trace as tr


def read(run):
    t = run.trace
    if t is None or not run.acked:
        return None
    lo, hi = t.window()
    calls = tr.clip(t.span_intervals("bench.submit_pipelined"), lo, hi)
    if not calls:
        return None
    dev = tr.intersect(calls, tr.busy(t, run.leader_device, lo, hi))
    return (tr.total(calls) - tr.total(dev)) / 1e3 / run.acked
