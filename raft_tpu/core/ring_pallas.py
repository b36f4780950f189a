"""Pallas TPU kernel for the masked ring-window payload write.

The XLA formulation of ``ring.write_window_cols`` (dynamic-slice read +
select + dynamic-update-slice, with a doubled-window rotation for the
wrap case) moves ~3x the window's bytes and splits into several
launch-bound ops (~8-10 us of the 31 us headline step, measured on v5e).
This kernel does the whole job in one ``pallas_call``:

- **grid over destination blocks** of the ring buffer, with a *modular*
  block index map ``((s // BR) + i) % (C // BR)`` — the ring wraparound
  falls out of block arithmetic, no lax.cond, no doubled window;
- the sub-block misalignment (``s % BR``) is handled by loading the two
  window blocks that can source a destination block and rotating their
  concatenation (``pltpu.roll`` with a dynamic shift);
- the merge (``sel ? win : cur``) happens in VMEM on the in-flight block;
  ``input_output_aliases`` writes the ring buffer in place.

Traffic: read cur once + read win once + write once = the masked-write
minimum. Requires ``C % BR == 0`` and ``B % BR == 0`` (RaftConfig already
guarantees C % B == 0 and C >= 2B; BR divides B below).

The XLA path in ``core.ring`` remains the reference and the non-TPU
fallback; ``tests/test_ring_pallas.py`` pins this kernel to it in
interpret mode, and ``bench.py`` asserts equality on real hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block_rows(B: int, C: int) -> int:
    """Row-block size: 128, which must divide both B and C. Smaller
    blocks are ruled out by Mosaic, not by choice: the term buffer's
    column blocks put the block size in the LANE dimension, which must be
    a multiple of 128 (ring._pallas_ok routes other shapes to XLA).
    128 x 192 lanes x 4 B = 96 KB per buffer fits VMEM with double
    buffering to spare."""
    if B % 128 or C % 128:
        raise ValueError(f"need 128 | B and 128 | C, got B={B}, C={C}")
    return 128


def _write_kernel(BR: int, C: int, meta_ref, win_ref, lanes_ref, buf_ref,
                  out_ref, prev_ref):
    """One destination block: merge the (rotated) window rows into the
    ring block, masked by window validity x accepting lanes.

    ``prev_ref`` (VMEM scratch) carries the previous grid step's window
    block: dest block i sources window rows from blocks i-1 and i (the
    ``s % BR`` misalignment), and the TPU grid runs sequentially, so the
    scratch saves re-fetching block i-1. At i=0 the scratch holds
    garbage, but every row it would source has jj < 0 and is masked."""
    s = meta_ref[0]
    count = meta_ref[1]
    i = pl.program_id(0)
    off = s % BR
    M = out_ref.shape[1]
    # window position of each row of this dest block: jj = BR*i - off + r
    r = jax.lax.broadcasted_iota(jnp.int32, (BR, M), 0)
    jj = BR * i - off + r
    lanes = lanes_ref[0, :] != 0                       # bool[M]
    sel = (jj >= 0) & (jj < count) & lanes[None, :]
    # source rows: win[jj] lives in block i-1 (scratch) for r < off and
    # block i (win_ref) for r >= off; rotate their concatenation so row
    # r holds win[jj]
    val2 = jnp.concatenate([prev_ref[:], win_ref[:]], axis=0)
    src = pltpu.roll(val2, off - BR, 0)[:BR]
    out_ref[:] = jnp.where(sel, src, buf_ref[:])
    prev_ref[:] = win_ref[:]


def _write_both_kernel(BR: int, C: int, meta_ref, win_ref, wint_ref,
                       acc_ref, last_ref, bufp_ref, buft_ref,
                       outp_ref, outt_ref, mm_ref, prevp_ref, prevt_ref):
    """Fused payload + term window write + mismatch detection, one
    destination block each grid step.

    Same geometry as ``_write_kernel`` for the payload; the term buffer
    ``[L, C]`` is column-blocked with the SAME modular block index (term
    col block == payload row block), so one grid drives both in-place
    updates. Along the way it reads the OLD term block anyway, so the
    step's conflict check (Raft §5.3: does an existing entry's term
    mismatch the window's?) is computed here too and accumulated into
    ``mm_ref`` — removing the separate window read + compare + reduce ops
    from the XLA step (~2 us measured). The per-replica accept mask
    (``acc_ref``, SMEM (L, 1)) expands to payload lanes in-kernel."""
    s = meta_ref[0]
    count = meta_ref[1]
    ws = meta_ref[2]                       # global log index of window row 0
    i = pl.program_id(0)
    off = s % BR
    M = outp_ref.shape[1]
    L = outt_ref.shape[0]
    W = M // L
    r = jax.lax.broadcasted_iota(jnp.int32, (BR, M), 0)
    jj = BR * i - off + r
    lane_rep = jax.lax.broadcasted_iota(jnp.int32, (BR, M), 1) // W
    lanes = (lane_rep == 0) & (acc_ref[0, 0] != 0)
    for l in range(1, L):
        lanes |= (lane_rep == l) & (acc_ref[l, 0] != 0)
    sel = (jj >= 0) & (jj < count) & lanes
    val2 = jnp.concatenate([prevp_ref[:], win_ref[:]], axis=0)
    src = pltpu.roll(val2, off - BR, 0)[:BR]
    outp_ref[:] = jnp.where(sel, src, bufp_ref[:])
    prevp_ref[:] = win_ref[:]
    # term: same window positions along the column axis. SMEM only
    # serves scalar loads, so the per-replica accept/last values gate
    # per-row vector ops in a statically unrolled loop over L.
    c1 = jax.lax.broadcasted_iota(jnp.int32, (1, BR), 1)
    jt1 = BR * i - off + c1
    valid1 = (jt1 >= 0) & (jt1 < count)                 # (1, BR)
    valt2 = jnp.concatenate([prevt_ref[:], wint_ref[:]], axis=1)
    srct = pltpu.roll(valt2, off - BR, 1)[:, :BR]       # (1, BR)
    curt = buft_ref[:]                                  # OLD terms (L, BR)
    # conflict check on the old content: an entry exists at this index
    # (widx <= last_index[row]) and its term differs from the window's
    @pl.when(i == 0)
    def _init():
        for l in range(L):
            mm_ref[0, l] = 0

    rows_t = []
    for l in range(L):
        cur_l = curt[l:l + 1, :]
        rows_t.append(jnp.where(
            valid1 & (acc_ref[l, 0] != 0), srct, cur_l
        ))
        # reduce the row's conflict mask to one scalar and accumulate in
        # SMEM (concatenating bool vectors trips an invalid vreg bitcast
        # in Mosaic; per-row select-then-reduce lowers cleanly)
        mm_row = valid1 & (ws + jt1 <= last_ref[l, 0]) & (cur_l != srct)
        mm_ref[0, l] |= jnp.max(jnp.where(mm_row, 1, 0))
    outt_ref[:] = jnp.concatenate(rows_t, axis=0)
    prevt_ref[:] = wint_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def write_window_both_tpu(buf_p: jax.Array, buf_t: jax.Array,
                          win: jax.Array, win_t: jax.Array, s: jax.Array,
                          count: jax.Array, ws: jax.Array,
                          accept: jax.Array, last_index: jax.Array,
                          interpret: bool = False):
    """Fused in-place masked window write of the payload ring
    (``buf_p [C, M]``) AND the term ring (``buf_t [L, C]``, per-slot
    value ``win_t [B]``), masked by per-replica ``accept [L]`` (expanded
    to payload lanes in-kernel) — plus the §5.3 conflict check against
    the old term content (``ws`` = global log index of window row 0,
    ``last_index [L]``). Returns (new_buf_p, new_buf_t, any_mm) where
    ``any_mm`` is i32[1, L], nonzero per replica with a conflicting
    existing entry inside the window."""
    C, M = buf_p.shape
    L = buf_t.shape[0]
    B = win.shape[0]
    BR = _pick_block_rows(B, C)
    G = B // BR + 1
    CB = C // BR
    WB = B // BR
    meta = jnp.stack([jnp.int32(s), jnp.int32(count), jnp.int32(ws)])
    acc = accept.astype(jnp.int32)[:, None]            # (L, 1)
    last = last_index.astype(jnp.int32)[:, None]       # (L, 1)
    wint = win_t.astype(jnp.int32)[None, :]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((BR, M), lambda i, m: (jnp.clip(i, 0, WB - 1), 0)),
            pl.BlockSpec((1, BR), lambda i, m: (0, jnp.clip(i, 0, WB - 1))),
            pl.BlockSpec((L, 1), lambda i, m: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((L, 1), lambda i, m: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BR, M), lambda i, m: (((m[0] // BR) + i) % CB, 0)),
            pl.BlockSpec((L, BR), lambda i, m: (0, ((m[0] // BR) + i) % CB)),
        ],
        out_specs=[
            pl.BlockSpec((BR, M), lambda i, m: (((m[0] // BR) + i) % CB, 0)),
            pl.BlockSpec((L, BR), lambda i, m: (0, ((m[0] // BR) + i) % CB)),
            pl.BlockSpec((1, L), lambda i, m: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((BR, M), jnp.int32),
            pltpu.VMEM((1, BR), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_write_both_kernel, BR, C),
        name="ring_write_both",
        out_shape=[
            jax.ShapeDtypeStruct((C, M), buf_p.dtype),
            jax.ShapeDtypeStruct((L, C), buf_t.dtype),
            jax.ShapeDtypeStruct((1, L), jnp.int32),
        ],
        grid_spec=grid_spec,
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(meta, win, wint, acc, last, buf_p, buf_t)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def write_window_cols_tpu(buf: jax.Array, win: jax.Array, s: jax.Array,
                          count: jax.Array, lane_sel: jax.Array,
                          interpret: bool = False) -> jax.Array:
    """Drop-in for ``ring.write_window_cols`` on TPU (see module doc)."""
    C, M = buf.shape
    B = win.shape[0]
    BR = _pick_block_rows(B, C)
    G = B // BR + 1                       # dest blocks a window can touch
    CB = C // BR
    WB = B // BR
    meta = jnp.stack([jnp.int32(s), jnp.int32(count)])
    lanes = lane_sel.astype(jnp.int32)[None, :]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(                 # win: block i (clamped at edges)
                (BR, M),
                lambda i, m: (jnp.clip(i, 0, WB - 1), 0),
            ),
            pl.BlockSpec((1, M), lambda i, m: (0, 0)),     # lane mask
            pl.BlockSpec(                 # ring dest block, modular
                (BR, M),
                lambda i, m: (((m[0] // BR) + i) % CB, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (BR, M),
            lambda i, m: (((m[0] // BR) + i) % CB, 0),
        ),
        scratch_shapes=[pltpu.VMEM((BR, M), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_write_kernel, BR, C),
        name="ring_write",
        out_shape=jax.ShapeDtypeStruct((C, M), buf.dtype),
        grid_spec=grid_spec,
        input_output_aliases={3: 0},      # buf (after 1 scalar-prefetch arg)
        interpret=interpret,
    )(meta, win, lanes, buf)
