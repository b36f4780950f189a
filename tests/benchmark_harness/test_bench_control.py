"""The check has teeth: the control (the reference log acknowledging after
the leader's row alone) and each fault planted in the timed path make a
whole run, set-up to check, come out not correct.

A round of the closed loop is fewer entries than a ring lap, so
``submit_pipelined`` runs the scanned program (``replicate_many``) on the
chip as here; the faults are planted there, at a 2^11 ring on the CPU."""

import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from bench_tiny import pallas_dispatch_of_this_process  # noqa: F401
from benchmark.harness import run_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def _values(result):
    return {k: v["value"] for k, v in result["checks"].items()}


CELLS = ["etcd3.put1000", "rs53.put1000"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(root, cell):
    r = run_cell(root, cell, 2**31 + 5, 0.5, False, time.perf_counter(),
                 control=True)
    v = _values(r)
    assert not r["correct"]
    assert v["ring_mismatch"] > 0 and v["exception"] == 0


# ------------------------------------------------------------------ faults
def _zero_half(payload, counts):
    """Leave out the second half of the entries of every batch (rows of
    a [T, B, L] stack with per-step counts, or of a [B, L] batch)."""
    n = jnp.asarray(counts)[..., None]
    rows = jnp.arange(payload.shape[-2])
    gone = (rows >= n // 2) & (rows < n)
    return jnp.where(gone[..., None], 0, payload)


def _alter_one(payload, counts):
    """Flip one bit of the first entry of every batch."""
    return payload.at[..., 0, 0].set(payload[..., 0, 0] ^ 1)


def _unchanged(old, new, leader, words):
    return old


FAULTS = {
    "half_batch": (_zero_half, None),
    "token_altered": (_alter_one, None),
    "state_unchanged": (None, _unchanged),
}


def _plant(monkeypatch, cls, method, fault):
    before, after = FAULTS[fault]
    orig = getattr(cls, method)

    def broken(self, state, payload, *args, **kw):
        # args: (counts, leader, term, ...)
        if before is not None:
            payload = before(payload, args[0])
        old = jax.tree.map(jnp.copy, state) if after is not None else None
        new, info = orig(self, state, payload, *args, **kw)
        if after is not None:
            new = after(old, new, int(args[1]), self.cfg.shard_words)
        return new, info

    monkeypatch.setattr(cls, method, broken)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_under_the_timed_path_is_not_correct(root, monkeypatch, cell,
                                                   fault):
    from raft_tpu.transport.device import SingleDeviceTransport

    _plant(monkeypatch, SingleDeviceTransport, "replicate_many", fault)
    r = run_cell(root, cell, 2**31 + 77, 0.5, False, time.perf_counter(),
                 require_chip=False)
    v = _values(r)
    assert not r["correct"], v
    assert v.get("ring_mismatch", 0) > 0 or v["exception"] > 0 \
        or v.get("lost", 0) > 0
