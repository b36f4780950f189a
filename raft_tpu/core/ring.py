"""Ring-buffer window access without generic scatter/gather.

XLA lowers 2-D advanced-index updates (``buf.at[rows, dest].set``) on TPU
to a *generic scatter* — a sequential per-element DMA loop (~80 ns per
updated row; a [3, 1024] window costs ~250 us). Worse, even a 1-D
``jnp.take`` with a traced index vector becomes a generic gather: a
``take(valid, idx)`` on a [1024] bool costs ~8 us on v5e — per call. The
protocol's windows are contiguous-with-wraparound in slot space, so every
window op here is expressed with only three primitives XLA compiles to
straight-line DMA on TPU:

- ``dynamic_slice`` / ``dynamic_update_slice`` on contiguous pieces;
- window-content *rotation* as ``concatenate([win, win])`` + one
  ``dynamic_slice`` at the rotation offset (no gather);
- validity masks as *arithmetic on an iota* (``rel < count``), never a
  gathered mask array.

Piece layout for a window of B slots starting at slot ``s``:
- piece A at ``min(s, C - B)`` — covers the tail part (or the whole window
  when it does not wrap);
- piece B at ``0`` — covers the wrapped head (a fully-masked rewrite of
  current bytes when the window does not wrap).

Requirements (validated by RaftConfig): ``C >= 2 * B`` so the two pieces
cannot overlap, and ``C % B == 0`` so the rotation offset
``(base - s) mod B`` equals ``(base - s) mod C`` on in-window lanes.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

# CI hook: route kernel-eligible shapes through the Pallas
# path in INTERPRET mode on non-TPU backends, so the kernel's composition
# with shard_map mesh programs is exercised before real multi-chip hardware
# runs it. Enabled per-process by env (RAFT_TPU_PALLAS_INTERPRET) or
# per-test by force_pallas_interpret().
_force_interpret = bool(os.environ.get("RAFT_TPU_PALLAS_INTERPRET"))


def force_pallas_interpret(on: bool) -> None:
    """Route ``_pallas_ok`` shapes through the Pallas kernels in interpret
    mode on non-TPU backends (CI composition testing)."""
    global _force_interpret
    _force_interpret = on


def pallas_interpret() -> bool:
    """Whether Pallas calls on the current backend must run in interpret
    mode (any backend without a Mosaic compiler — i.e. everything but
    TPU)."""
    return jax.default_backend() != "tpu"


def _pallas_ok(C: int, B: int) -> bool:
    """Whether the Pallas window-write kernel serves this shape: 128-row
    blocks dividing both the window and the ring (the term buffer's
    column blocks put the block size in the LANE dimension, which Mosaic
    requires to be a multiple of 128), on a TPU backend — or anywhere in
    interpret mode when forced (see above). Everything else uses the XLA
    reference formulation below."""
    if B % 128 or C % 128:
        return False
    return jax.default_backend() == "tpu" or _force_interpret


def _rot(win2: jax.Array, s: jax.Array, base: jax.Array, B: int,
         axis: int) -> jax.Array:
    """Window values aligned to piece ``base``: out[j] = win[(base+j-s) % B].

    ``win2`` is the window doubled along ``axis`` ([2B] there); the rotation
    is one contiguous dynamic_slice — in-window lanes get the right value,
    out-of-window lanes get junk the caller's mask discards.
    """
    offset = (base - s) % B
    starts = [jnp.int32(0)] * win2.ndim
    starts[axis] = offset
    sizes = list(win2.shape)
    sizes[axis] = B
    return lax.dynamic_slice(win2, starts, sizes)


def write_window_cols(buf: jax.Array, win: jax.Array, s: jax.Array,
                      count: jax.Array, lane_sel: jax.Array) -> jax.Array:
    """Masked write of slot-major window ``win`` at slots [s, s+B) mod C.

    buf: [C, M] folded payload (core.state layout); win: [B, M]; s: i32[]
    start slot; count: i32[] window rows to write (a prefix); lane_sel:
    bool[M] lanes (per-replica word blocks) that accept. This is the
    hot-path payload write.

    Fast path: when the window does not wrap (``s <= C - B`` — all but 1
    in C/B steps), the write is ONE read-merge-update at ``s`` with no
    rotation and no doubled window. The generic two-piece rotated path
    runs only under ``lax.cond`` for the wrapping minority — measured on
    v5e this halves the payload path's HBM traffic (the doubled-window
    concat and the always-on fully-masked piece-B merge were ~8 us/step
    of the 31 us headline step).
    """
    C, B = buf.shape[0], win.shape[0]
    M = buf.shape[1]
    if _pallas_ok(C, B):
        # TPU: one pallas_call does the whole masked merge in place with
        # modular-block wraparound — minimum HBM traffic, one launch
        # (core.ring_pallas; pinned to this XLA path by tests).
        from raft_tpu.core.ring_pallas import write_window_cols_tpu

        return write_window_cols_tpu(
            buf, win, s, count, lane_sel, interpret=pallas_interpret()
        )
    return write_window_cols_xla(buf, win, s, count, lane_sel)


def write_window_cols_xla(buf: jax.Array, win: jax.Array, s: jax.Array,
                          count: jax.Array, lane_sel: jax.Array) -> jax.Array:
    """The pure-XLA formulation (reference semantics for the Pallas
    kernel, and the non-TPU execution path)."""
    C, B = buf.shape[0], win.shape[0]
    M = buf.shape[1]
    j = jnp.arange(B, dtype=jnp.int32)

    def fast(buf):
        cur = lax.dynamic_slice(buf, (s, 0), (B, M))
        sel = (j < count)[:, None] & lane_sel[None, :]
        return lax.dynamic_update_slice(buf, jnp.where(sel, win, cur), (s, 0))

    def wrap(buf):
        win2 = jnp.concatenate([win, win], axis=0)
        for base in (jnp.minimum(s, C - B), jnp.zeros_like(s)):
            cur = lax.dynamic_slice(buf, (base, 0), (B, M))
            rel = (base + j - s) % C
            sel = (rel < count)[:, None] & lane_sel[None, :]
            win_at = _rot(win2, s, base, B, axis=0)
            buf = lax.dynamic_update_slice(
                buf, jnp.where(sel, win_at, cur), (base, 0)
            )
        return buf

    # NOTE both branches must WRITE buf (DUS): an identity branch breaks
    # XLA's donated-buffer aliasing through the cond and forces a full
    # ring-buffer copy (~100 us for the 25 MB headline ring — measured).
    return lax.cond(s <= C - B, fast, wrap, buf)


def read_window_cols(buf: jax.Array, s: jax.Array, B: int) -> jax.Array:
    """Slot-major window [s, s+B) mod C of ``buf`` [C, M] -> [B, M].
    One dynamic_slice when the window does not wrap; the three-copy
    stitch only under ``lax.cond`` for the wrapping minority."""
    C = buf.shape[0]

    def fast(buf):
        return lax.dynamic_slice(buf, (s, 0), (B, buf.shape[1]))

    def wrap(buf):
        sA = jnp.minimum(s, C - B)
        a = lax.dynamic_slice(buf, (sA, 0), (B, buf.shape[1]))
        b = lax.dynamic_slice(buf, (0, 0), (B, buf.shape[1]))
        ab = jnp.concatenate([a, b], axis=0)
        # piece A starts at sA and piece B continues at exactly
        # sA + B == C in the wrap case, so the stitched window is
        # ab[s - sA : s - sA + B]
        return lax.dynamic_slice(ab, (s - sA, 0), (B, buf.shape[1]))

    return lax.cond(s <= C - B, fast, wrap, buf)


def write_window_rows(buf: jax.Array, win_t: jax.Array, s: jax.Array,
                      count: jax.Array, accept: jax.Array) -> jax.Array:
    """Masked write of a per-slot value window into row-major ``buf``.

    buf: [L, C] (the log_term array); win_t: i32[B] value per window slot
    (identical for every accepting row — a window carries one term per
    entry); s: start slot; count: rows-to-write prefix; accept: bool[L].
    """
    L, C = buf.shape
    B = win_t.shape[0]
    j = jnp.arange(B, dtype=jnp.int32)

    def fast(buf):
        cur = lax.dynamic_slice(buf, (0, s), (L, B))
        sel = accept[:, None] & (j < count)[None, :]
        return lax.dynamic_update_slice(
            buf, jnp.where(sel, win_t[None, :], cur), (0, s)
        )

    def wrap(buf):
        win2 = jnp.concatenate([win_t, win_t], axis=0)
        for base in (jnp.minimum(s, C - B), jnp.zeros_like(s)):
            cur = lax.dynamic_slice(buf, (0, base), (L, B))
            rel = (base + j - s) % C
            sel = accept[:, None] & (rel < count)[None, :]
            win_at = _rot(win2, s, base, B, axis=0)
            buf = lax.dynamic_update_slice(
                buf, jnp.where(sel, win_at[None, :], cur), (0, base)
            )
        return buf

    return lax.cond(s <= C - B, fast, wrap, buf)


def read_window(buf: jax.Array, s: jax.Array, B: int) -> jax.Array:
    """Window [s, s+B) mod C of row-major ``buf`` [L, C, ...] -> [L, B, ...].
    One dynamic_slice in the (common) non-wrapping case."""
    C = buf.shape[1]
    zeros = (0,) * (buf.ndim - 2)
    size = (buf.shape[0], B) + buf.shape[2:]

    def fast(buf):
        return lax.dynamic_slice(buf, (0, s) + zeros, size)

    def wrap(buf):
        sA = jnp.minimum(s, C - B)
        a = lax.dynamic_slice(buf, (0, sA) + zeros, size)
        b = lax.dynamic_slice(buf, (0, 0) + zeros, size)
        ab = jnp.concatenate([a, b], axis=1)
        return lax.dynamic_slice(ab, (0, s - sA) + zeros, size)

    return lax.cond(s <= C - B, fast, wrap, buf)
