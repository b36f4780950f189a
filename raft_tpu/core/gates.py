"""Kernel equivalence gates: the Pallas kernels byte-asserted against
their reference formulations on the backend they run on.

CI runs the kernels in interpret mode only, and two regimes are beyond
what interpret mode can vouch for: the ring-write kernel's in-place
modular-block writes, and the single-launch pipeline revisiting ring
blocks within one ``pallas_call`` under input/output aliasing. These
gates run the compiled kernels on the chip and compare every byte with
the XLA formulation or the per-step fused scan. They raise
``AssertionError`` on the first mismatch and never skip themselves: the
caller decides where they run (``chip_smoke.py`` on the chip, ``bench.py``
before its legs).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from raft_tpu.config import RaftConfig
from raft_tpu.core.state import fold_batch, init_state


def ring_kernel_gate(rng, capacity: int = 1 << 15, batch: int = 1024,
                     interpret: bool = False) -> dict:
    """The fused ring-write kernel (payload + term rings + conflict
    check) against ``write_window_cols_xla`` / ``write_window_rows`` on
    wrapping, partial-count, empty and conflicting windows."""
    from raft_tpu.core.ring import write_window_cols_xla, write_window_rows
    from raft_tpu.core.ring_pallas import write_window_both_tpu

    C, B, M, L = capacity, batch, 192, 3
    cases = [(0, B), (77, B - 24), (C - B + B // 2 - 1, B),
             (C - 1, B // 4 + 44), (9, 0)]
    for s, count in cases:
        buf_p = rng.integers(-2**31, 2**31 - 1, (C, M), dtype=np.int32)
        buf_t = rng.integers(1, 6, (L, C), dtype=np.int32)
        win = rng.integers(-2**31, 2**31 - 1, (B, M), dtype=np.int32)
        win_t = rng.integers(1, 6, B, dtype=np.int32)
        accept = rng.random(L) < 0.7
        lanes = np.repeat(accept, M // L)
        ws = s + 1
        last = rng.integers(0, ws + B, L).astype(np.int32)
        gp, gt, gmm = write_window_both_tpu(
            jnp.asarray(buf_p), jnp.asarray(buf_t), jnp.asarray(win),
            jnp.asarray(win_t), jnp.int32(s), jnp.int32(count),
            jnp.int32(ws), jnp.asarray(accept), jnp.asarray(last),
            interpret=interpret,
        )
        wp = write_window_cols_xla(
            jnp.asarray(buf_p), jnp.asarray(win), jnp.int32(s),
            jnp.int32(count), jnp.asarray(lanes),
        )
        wt = write_window_rows(
            jnp.asarray(buf_t), jnp.asarray(win_t), jnp.int32(s),
            jnp.int32(count), jnp.asarray(accept),
        )
        np.testing.assert_array_equal(
            np.asarray(gp), np.asarray(wp),
            err_msg=f"ring kernel payload diverges at s={s}",
        )
        np.testing.assert_array_equal(
            np.asarray(gt), np.asarray(wt),
            err_msg=f"ring kernel terms diverge at s={s}",
        )
        widx = ws + np.arange(B)
        my_win_t = buf_t[:, (s + np.arange(B)) % C]
        want_mm = (
            (widx[None, :] <= last[:, None])
            & (my_win_t != win_t[None, :])
            & (np.arange(B) < count)[None, :]
        ).any(axis=1)
        np.testing.assert_array_equal(
            np.asarray(gmm)[0] != 0, want_mm,
            err_msg=f"ring kernel conflict check diverges at s={s}",
        )
    return {"ring_gate_cases": len(cases), "ring_gate_capacity": C}


def pipeline_lap_gate(rng, batch: int = 1024,
                      interpret: bool = False) -> dict:
    """The single-launch pipeline kernel in the ring-LAP regime (a
    12-step flight over a ring of 4 batches revisits every destination block
    within one launch) against the per-step fused scan, byte for byte:
    the write-only turnover branch, the aliased pipeline on the same
    all-accept flight, the aliased pipeline with a never-accepting slow
    row, and the RS(5,3) lane geometry with in-kernel parity plus its
    decode from a non-systematic row subset."""
    from raft_tpu.core.step_pallas import (
        steady_pipeline_tpu, steady_scan_replicate_tpu,
    )

    cfg = RaftConfig(batch_size=batch, log_capacity=4 * batch)  # T: 3 laps
    T = 12
    wins4 = jnp.stack([
        jnp.asarray(fold_batch(rng.integers(
            0, 256, (cfg.batch_size, cfg.entry_bytes), dtype=np.uint8
        ), cfg.rows))
        for _ in range(4)
    ])
    counts = jnp.full((T,), cfg.batch_size, jnp.int32)
    xs = jnp.stack([wins4[t % 4] for t in range(T)])
    cases = [
        (np.zeros(3, bool), True),
        (np.zeros(3, bool), False),
        (np.array([False, False, True]), False),
    ]
    for slow, allow in cases:
        args = (jnp.int32(0), jnp.int32(1), jnp.ones(3, bool),
                jnp.asarray(slow), jnp.int32(0), jnp.int32(0), None,
                jnp.int32(1))
        st_s, _ = steady_scan_replicate_tpu(
            init_state(cfg), xs, counts, *args, commit_quorum=None,
            stack_infos=False, interpret=interpret,
        )
        st_p, _ = steady_pipeline_tpu(
            init_state(cfg), wins4, counts, *args, commit_quorum=None,
            allow_turnover=allow, interpret=interpret,
        )
        for f in ("term", "voted_for", "last_index", "commit_index",
                  "match_index", "match_term", "log_term", "log_payload"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st_s, f)), np.asarray(getattr(st_p, f)),
                err_msg=f"pipeline lap regime diverges: {f} "
                        f"(slow={slow}, turnover={allow})",
            )

    from raft_tpu.ec.kernels import fold_data_lanes, parity_consts
    from raft_tpu.ec.reconstruct import reconstruct
    from raft_tpu.ec.rs import RSCode

    ecfg = RaftConfig(n_replicas=5, entry_bytes=264, batch_size=batch,
                      log_capacity=4 * batch, rs_k=3, rs_m=2,
                      transport="single")
    consts = parity_consts(5, 3)
    raw = rng.integers(
        0, 256, (T, ecfg.batch_size, ecfg.entry_bytes), dtype=np.uint8
    )
    ewins = jnp.stack([fold_data_lanes(jnp.asarray(raw[t]))
                       for t in range(T)])
    eargs = (jnp.int32(0), jnp.int32(1), jnp.ones(5, bool),
             jnp.zeros(5, bool), jnp.int32(0), jnp.int32(0), None,
             jnp.int32(1))
    st_s, _ = steady_scan_replicate_tpu(
        init_state(ecfg), ewins, counts, *eargs,
        commit_quorum=ecfg.commit_quorum, stack_infos=False,
        ec_consts=consts, interpret=interpret,
    )
    st_p, _ = steady_pipeline_tpu(
        init_state(ecfg), ewins, counts, *eargs,
        commit_quorum=ecfg.commit_quorum, ec_consts=consts,
        interpret=interpret,
    )
    for f in ("last_index", "commit_index", "log_term", "log_payload"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st_s, f)), np.asarray(getattr(st_p, f)),
            err_msg=f"EC pipeline lap regime diverges: {f}",
        )
    hi = T * ecfg.batch_size
    lo = hi - ecfg.log_capacity + 1
    got = reconstruct(st_p, RSCode(5, 3), [1, 2, 4], lo, hi)
    np.testing.assert_array_equal(
        got, raw.reshape(-1, ecfg.entry_bytes)[-ecfg.log_capacity:],
        err_msg="EC pipeline lap decode != raw bytes",
    )
    return {"lap_gate_cases": len(cases) + 1, "lap_gate_steps": T}
