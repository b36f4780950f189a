"""Share of the traced window in which no op ran on the leader's chip."""

from benchmark import trace as tr


def read(run):
    return tr.idle_pct(run.trace, run.leader_device)
