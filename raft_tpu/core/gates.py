"""Kernel equivalence gates: the Pallas kernels byte-asserted against
their reference formulations on the backend they run on.

CI runs the kernels in interpret mode only, and the ring-write kernel's
in-place modular-block writes are beyond what interpret mode can vouch
for. This gate runs the compiled kernel on the chip and compares every
byte with the XLA formulation. It raises ``AssertionError`` on the first
mismatch and never skips itself: the caller decides where it runs
(``chip_smoke.py`` on the chip, ``bench.py`` before its legs).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


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

