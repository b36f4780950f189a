"""Device time of the window's replication kernels (its Pallas ops: the
ring write and step kernels, and the RS encode where the configuration
codes) per acknowledged entry."""

from benchmark import trace as tr


def read(run):
    s = tr.kernel_seconds(run.trace, run.leader_device, tr.KERNEL_OPS)
    if not s or not run.acked:
        return None
    return s / run.acked * 1e6
