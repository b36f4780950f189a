"""The replication kernels' share of the HBM roofline: the bytes the
acknowledged entries need (benchmark.roofline.entry_bytes) over the
device time of the window's Pallas ops, over the chip's peak bandwidth."""

from benchmark import roofline
from benchmark import trace as tr


def read(run):
    s = tr.kernel_seconds(run.trace, run.leader_device, tr.KERNEL_OPS)
    if not s or not run.acked:
        return None
    need = roofline.entry_bytes(run.raft) * run.acked
    return 100.0 * need / s / run.peaks()["hbm_bytes_per_s"]
