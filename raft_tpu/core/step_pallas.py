"""The ENTIRE steady-state replication step as one Pallas TPU kernel.

``core.step.replicate_step`` with ``repair=False`` (the steady program) is
one fused window kernel (~4.9 us) surrounded by ~15 tiny XLA ops — frontier
accounting, accept masks, match bookkeeping, the quorum commit — that cost
~5 us of launch/gap overhead per step on v5e (docs/PERF.md "Where a step's
time goes", round 3). On the resident (single-device) layout every one of
those ops touches only [L]-sized vectors and scalars, so they fold into the
window kernel's scalar core for free:

- the window merge (payload + term + the Raft §5.3 conflict check) keeps
  ``ring_pallas``'s geometry: grid over destination blocks, modular block
  index map for ring wraparound, ``pltpu.roll`` for sub-block misalignment —
  but with larger 512-row blocks when the shape allows (fewer grid steps);
- the *prologue* (grid step 0) recomputes the frontier accounting
  (room/backpressure/ingest gating) and the heard/accept/verified-match
  masks in SMEM scalars, straight from the packed state vectors — the only
  outside ops left are the start-slot computation the grid's index maps
  need and the one [L, 1] prev-term column slice (feeding the aliased term
  ring in as a second read operand would force a defensive ring copy);
- the *epilogue* (last grid step) advances last/match/commit, adopts terms,
  and computes the quorum commit (counting k-th order statistic, unrolled
  over L <= 9 rows) — all scalar SMEM arithmetic.

The six [L]-sized state vectors travel PACKED as one (6, L) i32 array: six
separate SMEM operands/results cost six relayout copies + reduces per scan
step (~1.7 us measured); packed, the scan carry moves one tiny array, and
``steady_scan_replicate_tpu`` packs/unpacks once per whole scan. Per-scan
constants (leader, term, floors, quorum, masks) ride one hoisted params
operand; the per-step operand set is just {start slot, count, prev column}.

The steady frontier window always carries entries of the leader's CURRENT
term, so the per-slot term window degenerates to one scalar and the term
ring write needs no rotation machinery at all.

The §5.4.2 current-term commit gate uses a host-supplied ``term_floor``
(first log index of the leader's current term) instead of reading the
candidate slot's term from the ring: ``commit_cand >= term_floor`` is
equivalent (entries >= floor hold the leader's term by construction; the
engine maintains the floor at election and truncation time) and removes a
data-dependent ring read the grid could not serve.

Only the resident layout takes this path (``SingleDeviceComm`` — the
benchmark and the CI fast path): collectives degenerate to row indexing,
which the kernel's scalar loops do directly. The mesh program keeps the
``core.step`` formulation whose Comm ops lower to real ICI collectives.
``core.step.replicate_step`` dispatches here; the XLA formulation remains
the reference semantics (equivalence pinned by tests/test_steady_fused.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.state import NO_VOTE, ReplicaState, slot_of

# per-scan params operand layout (1-D SMEM, hoisted out of the loop).
# _MYROW is the local replica row's GLOBAL id in the mesh variant (the
# per-device data plane, core.step_mesh); -1 and unread on the resident
# layout.
_LEADER, _LTERM, _TFLOOR, _RFLOOR, _FPT, _QUORUM, _MYROW = range(7)
_NPARAMS = 7

# packed state-vector rows (the (6, L) SMEM operand/result)
_VT, _VV, _VL, _VC, _VMI, _VMT = range(6)

# mask-operand rows (the (3, L) SMEM operand)
_MAL, _MSL, _MAK = range(3)

# scratch rows in the (6, L) SMEM scalar scratch: per-row masks + the
# frontier scalars the prologue derives (stored in row _FRS, cols 0..2)
_ACC, _HEARD, _MEFF, _MM, _FRS = range(5)
_F_COUNT, _F_WS, _F_LCUR = range(3)


def _pick_br(B: int, C: int) -> int:
    """Row-block size: 256 when it divides both the window and the ring,
    else 128. Measured on v5e (headline shape): 256 beats 128 by ~1%
    (fewer grid steps) and 512 LOSES ~18% (3-step grids pipeline in/out
    DMA poorly). Must stay a multiple of 128: the term buffer's column
    blocks put BR in the LANE dimension (``ring._pallas_ok`` routes other
    shapes to the XLA formulation)."""
    if B % 256 == 0 and C % 256 == 0:
        return 256
    return 128


def _encode_parity_lanes(src, pconsts, BR, W):
    """Append the RS parity lane blocks to a (BR, k*W) data-lane window
    using the packed-i32 GF(2^8) multiply."""
    m_par, k_data = pconsts.shape[0], pconsts.shape[1]
    parts = [src]
    for p in range(m_par):
        acc_p = jnp.zeros((BR, W), jnp.int32)
        for j in range(k_data):
            acc_p ^= _mul_const_packed(
                src[:, j * W:(j + 1) * W], pconsts[p, j]
            )
        parts.append(acc_p)
    return jnp.concatenate(parts, axis=1)


def _mul_const_packed(x, c_bits):
    """GF(2^8) multiply of every byte of packed-i32 ``x`` by the constant
    whose bit-decomposition products are ``c_bits`` (u8[8], c_bits[i] =
    mul(c, 1<<i)): XOR over set bits i of ((x >> i) & 0x01010101) *
    c_bits[i]. Byte-parallel within each i32 word — the isolated bit mask
    makes every byte slot 0 or 1, so the integer multiply never carries
    across byte boundaries, and the arithmetic right shift's sign fill
    sits above every masked bit (i <= 7, mask bits <= 24). This is the
    ec.kernels bit-sliced formulation restated on the folded i32 layout,
    so the parity encode can run INSIDE the window-merge kernel."""
    acc = jnp.zeros_like(x)
    for i in range(8):
        c = int(c_bits[i])
        if c:
            acc ^= ((x >> i) & 0x01010101) * c
    return acc


# ``local`` (static) selects the MESH data plane (core.step_mesh): the
# scalar core still simulates ALL L(=R) rows from the gathered state
# vectors — replicated SPMD work, identical on every device — but the
# VMEM buffers hold only the local replica row's lanes (payload (C, W),
# terms (1, C)), selected by the _MYROW param. The §5.3 conflict bit and
# the next-prev stash, which read OTHER rows' ring content the device
# does not hold, are replaced by their closed forms under the engine's
# steady-program invariants (see core.step_mesh module doc): an
# accepting row's tail lands exactly at the window end (a stale suffix
# always conflicts — no follower holds current-term entries beyond the
# leader's tail), and the next window's prev term is ``lterm`` for
# accepting rows and provably != lterm for the rest (sentinel -1).
def _steady_kernel(BR: int, C: int, L: int, pconsts, local, s_ref,
                   cnt_ref, prevt_ref, par_ref, vec_ref, msks_ref,
                   win_ref, bufp_ref, buft_ref,
                   outp_ref, outt_ref, vec_o, match_o, scal_o, nextp_o,
                   prevp_ref, msk_ref):
    s = s_ref[0]
    leader = par_ref[0, _LEADER]
    lterm = par_ref[0, _LTERM]
    i = pl.program_id(0)
    off = s % BR
    M = outp_ref.shape[1]
    W = M if local else M // L
    legit = lterm >= 1

    # ---- prologue: frontier accounting + per-row masks (grid step 0) -----
    @pl.when(i == 0)
    def _prologue():
        last0_l = vec_ref[_VL, 0]
        commit0_l = vec_ref[_VC, 0]
        term0_l = vec_ref[_VT, 0]
        for l in range(1, L):
            pick = leader == l
            last0_l = jnp.where(pick, vec_ref[_VL, l], last0_l)
            commit0_l = jnp.where(pick, vec_ref[_VC, l], commit0_l)
            term0_l = jnp.where(pick, vec_ref[_VT, l], term0_l)
        leader_current = legit & (term0_l <= lterm)
        room = C - (last0_l - commit0_l)
        B = BR * (pl.num_programs(0) - 1)
        count = jnp.where(
            leader_current,
            jnp.minimum(jnp.clip(cnt_ref[0, 0], 0, B),
                        jnp.maximum(room, 0)),
            0,
        )
        ws = last0_l + 1
        leader_last = last0_l + count
        msk_ref[_FRS, _F_COUNT] = count
        msk_ref[_FRS, _F_WS] = ws
        msk_ref[_FRS, _F_LCUR] = leader_current.astype(jnp.int32)

        prev_ts = [prevt_ref[l, 0] for l in range(L)]
        # the window's prev term: the leader's ring value, overridden by
        # the attested term below the leader's ring-validity floor, and 0
        # for the log head (core.step.leader_prev_term)
        ring_prev = prev_ts[0]
        for l in range(1, L):
            ring_prev = jnp.where(leader == l, prev_ts[l], ring_prev)
        prev_term = jnp.where(
            ws - 1 < par_ref[0, _RFLOOR], par_ref[0, _FPT], ring_prev
        )
        prev_term = jnp.where(ws == 1, 0, prev_term)
        for l in range(L):
            has_prev = (ws == 1) | (
                (vec_ref[_VL, l] >= ws - 1) & (prev_ts[l] == prev_term)
            )
            heard = (msks_ref[_MAL, l] != 0) & legit & \
                (lterm >= vec_ref[_VT, l])
            ingest = (leader == l) & leader_current
            m0 = jnp.where(vec_ref[_VMT, l] == lterm, vec_ref[_VMI, l], 0)
            m0 = jnp.where(ingest, leader_last, m0)
            acc = (heard & (msks_ref[_MSL, l] == 0) & has_prev) | ingest
            msk_ref[_ACC, l] = acc.astype(jnp.int32)
            msk_ref[_HEARD, l] = heard.astype(jnp.int32)
            msk_ref[_MEFF, l] = m0
            msk_ref[_MM, l] = 0

    count = msk_ref[_FRS, _F_COUNT]
    ws = msk_ref[_FRS, _F_WS]

    # ---- window merge: payload + uniform-term write + §5.3 check ---------
    r = jax.lax.broadcasted_iota(jnp.int32, (BR, M), 0)
    jj = BR * i - off + r
    if local:
        myr = par_ref[0, _MYROW]
        acc_my = msk_ref[_ACC, 0]
        for l in range(1, L):
            acc_my = jnp.where(myr == l, msk_ref[_ACC, l], acc_my)
        sel = (jj >= 0) & (jj < count) & (acc_my != 0)
    else:
        lane_rep = jax.lax.broadcasted_iota(jnp.int32, (BR, M), 1) // W
        lanes = (lane_rep == 0) & (msk_ref[_ACC, 0] != 0)
        for l in range(1, L):
            lanes |= (lane_rep == l) & (msk_ref[_ACC, l] != 0)
        sel = (jj >= 0) & (jj < count) & lanes
    val2 = jnp.concatenate([prevp_ref[:], win_ref[:]], axis=0)
    src = pltpu.roll(val2, off - BR, 0)[:BR]
    if pconsts is not None:
        # RS parity encode fused into the merge: the window carries only
        # the k data-lane blocks; parity block p is computed right here,
        # one VMEM traversal for encode + ring write (pconsts is the
        # (rows-k, k, 8) bit-decomposition table of the code's parity
        # matrix, baked at trace time).
        src = _encode_parity_lanes(src, pconsts, BR, W)           # (BR, M)
    outp_ref[:] = jnp.where(sel, src, bufp_ref[:])
    prevp_ref[:] = win_ref[:]

    c1 = jax.lax.broadcasted_iota(jnp.int32, (1, BR), 1)
    jt1 = BR * i - off + c1
    valid1 = (jt1 >= 0) & (jt1 < count)                 # (1, BR)
    curt = buft_ref[:]                          # OLD terms (L or 1, BR)
    if local:
        # only the local row's term ring exists here; the conflict bit is
        # closed-form in the epilogue (module NOTE above)
        outt_ref[:] = jnp.where(valid1 & (acc_my != 0), lterm, curt)
    else:
        rows_t = []
        for l in range(L):
            cur_l = curt[l:l + 1, :]
            rows_t.append(jnp.where(
                valid1 & (msk_ref[_ACC, l] != 0), lterm, cur_l
            ))
            mm_row = valid1 & (ws + jt1 <= vec_ref[_VL, l]) & \
                (cur_l != lterm)
            msk_ref[_MM, l] |= jnp.max(jnp.where(mm_row, 1, 0))
        outt_ref[:] = jnp.concatenate(rows_t, axis=0)

    # ---- stash the NEXT step's prev-term column while it is in VMEM ------
    # The next frontier window's prev entry is this window's last valid
    # entry (slot q); handing its term column to the next scan iteration
    # through the carry removes the host-graph slice of the term ring
    # whose data dependency serialized each iteration against the previous
    # kernel's output. (Local mode computes the closed form in the
    # epilogue instead — other rows' ring content is not held here.)
    if not local:
        q = (s + count - 1) % C
        d = ((s // BR) + i) % (C // BR)

        @pl.when((count > 0) & (d == q // BR))
        def _stash_next_prev():
            sel_q = c1 == q % BR
            for l in range(L):
                nextp_o[l, 0] = jnp.sum(jnp.where(sel_q, rows_t[l], 0))

    # ---- epilogue: state advance + quorum commit (last grid step) --------
    @pl.when(i == pl.num_programs(0) - 1)
    def _epilogue():
        leader_current = msk_ref[_FRS, _F_LCUR] != 0
        we = ws + count - 1
        matches = []
        meffs = []
        heards = []
        for l in range(L):
            acc = msk_ref[_ACC, l] != 0
            mm = msk_ref[_MM, l] != 0
            heard = msk_ref[_HEARD, l] != 0
            m0 = msk_ref[_MEFF, l]
            last0 = vec_ref[_VL, l]
            if local:
                # closed form (module NOTE): an accepting row's tail is
                # exactly the window end — a consistent suffix beyond it
                # cannot exist (it would be current-term entries past the
                # leader's tail), so a longer tail always conflicts and
                # truncates to ``we``
                vec_o[_VL, l] = jnp.where(acc & (count > 0), we, last0)
            else:
                # no conflict: keep any consistent suffix beyond the
                # window; conflict: truncate to the window end (§5.3)
                vec_o[_VL, l] = jnp.where(
                    acc,
                    jnp.where(mm, jnp.maximum(we, ws - 1),
                              jnp.maximum(last0, we)),
                    last0,
                )
            m1 = jnp.where(acc, jnp.maximum(m0, we), m0)
            meffs.append(m1)
            heards.append(heard)
            matches.append(jnp.where(msks_ref[_MAK, l] != 0, m1, 0))
            match_o[0, l] = matches[l]
        # counting k-th order statistic (quorum.commit_from_match)
        cand = jnp.int32(0)
        for l in range(L):
            cnt = jnp.int32(0)
            for j in range(L):
                cnt += (matches[j] >= matches[l]).astype(jnp.int32)
            cand = jnp.maximum(
                cand, jnp.where(cnt >= par_ref[0, _QUORUM], matches[l], 0)
            )
        commit_ok = legit & (cand >= 1) & (cand >= par_ref[0, _TFLOOR])
        lcommit = vec_ref[_VC, 0]
        for l in range(1, L):
            lcommit = jnp.where(leader == l, vec_ref[_VC, l], lcommit)
        g_commit = jnp.where(
            commit_ok, jnp.maximum(lcommit, cand), lcommit
        )
        max_term = jnp.int32(0)
        for l in range(L):
            heard = heards[l]
            ingest = (leader == l) & leader_current
            t0 = vec_ref[_VT, l]
            adopt = heard & (lterm > t0)
            t1 = jnp.where(heard, jnp.maximum(t0, lterm), t0)
            vec_o[_VT, l] = t1
            vec_o[_VV, l] = jnp.where(adopt, NO_VOTE, vec_ref[_VV, l])
            my_commit = jnp.where(
                leader == l, g_commit, jnp.minimum(g_commit, meffs[l])
            )
            vec_o[_VC, l] = jnp.where(
                (heard & (msks_ref[_MSL, l] == 0)) | ingest,
                jnp.maximum(vec_ref[_VC, l], my_commit),
                vec_ref[_VC, l],
            )
            vec_o[_VMI, l] = jnp.where(
                heard | ingest, meffs[l], vec_ref[_VMI, l]
            )
            vec_o[_VMT, l] = jnp.where(
                heard | ingest, lterm, vec_ref[_VMT, l]
            )
            max_term = jnp.maximum(
                max_term, jnp.where(msks_ref[_MAL, l] != 0, t1, 0)
            )
        scal_o[0, 0] = g_commit
        scal_o[0, 1] = max_term
        scal_o[0, 2] = count
        # next step's window start slot: slot_of(leader_last_new + 1)
        scal_o[0, 3] = (ws - 1 + count) % C

        if local:
            # closed-form next-prev column (module NOTE): accepting rows
            # just wrote ``lterm`` at the window tail; for every other
            # row the next window's prev slot provably does not hold
            # lterm, so any value != lterm preserves the accept
            # booleans — the -1 sentinel makes the mismatch explicit
            for l in range(L):
                nextp_o[l, 0] = jnp.where(
                    count > 0,
                    jnp.where(msk_ref[_ACC, l] != 0, lterm,
                              jnp.int32(-1)),
                    prevt_ref[l, 0],
                )
        else:
            @pl.when(count == 0)
            def _next_prev_passthrough():
                for l in range(L):
                    nextp_o[l, 0] = prevt_ref[l, 0]


def _start_slot_and_prev(vecs, log_term, leader, cap, L):
    """The one piece the grid cannot compute for itself: the window start
    slot (its index maps consume it) and the prev-term column — one tiny
    fused XLA region per step."""
    s, prev_slot = _frontier_slots(vecs[_VL, leader], cap)
    prev_col = jax.lax.dynamic_slice(
        log_term, (jnp.int32(0), prev_slot), (L, 1)
    ).astype(jnp.int32)
    return s, prev_col


def _frontier_slots(last0_l, cap):
    """Window start slot and prev-term slot for a leader whose tail is
    ``last0_l`` — shared by the resident ``_start_slot_and_prev`` and the
    mesh ``core.step_mesh._gather_plane`` so the frontier geometry
    (including the max(ws-1, 1) head clamp) can never drift between the
    two layouts."""
    ws = last0_l + 1
    s = slot_of(ws, cap)
    prev_slot = slot_of(jnp.maximum(ws - 1, 1), cap)
    return jnp.int32(s)[None], prev_slot


def _invoke(s, cnt, prev_col, params, vecs, masks, win, log_payload,
            log_term, interpret, pconsts=None, local=False):
    cap, M = log_payload.shape
    # local (mesh) mode: the scalar plane is R-wide (the gathered vecs)
    # while the ring buffers hold one row's lanes — see _steady_kernel.
    L = vecs.shape[1]
    TL = log_term.shape[0]       # term-ring rows held here (1 when local)
    B, Mk = win.shape            # Mk = k*W data lanes when pconsts is set
    if (Mk != M) != (pconsts is not None):
        raise ValueError(
            f"window lanes {Mk} vs payload lanes {M}: data-lane-only "
            "windows require ec_consts (in-kernel parity), full-lane "
            "windows must not pass it"
        )
    BR = _pick_br(B, cap)
    G = B // BR + 1
    CB = cap // BR
    WB = B // BR

    def smem(shape):
        return pl.BlockSpec(shape, lambda i, m: (0, 0),
                            memory_space=pltpu.SMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            smem((1, 1)),
            smem((L, 1)),
            smem((1, _NPARAMS)),
            smem((6, L)),
            smem((3, L)),
            pl.BlockSpec((BR, Mk), lambda i, m: (jnp.clip(i, 0, WB - 1), 0)),
            pl.BlockSpec((BR, M), lambda i, m: (((m[0] // BR) + i) % CB, 0)),
            pl.BlockSpec((TL, BR), lambda i, m: (0, ((m[0] // BR) + i) % CB)),
        ],
        out_specs=[
            pl.BlockSpec((BR, M), lambda i, m: (((m[0] // BR) + i) % CB, 0)),
            pl.BlockSpec((TL, BR), lambda i, m: (0, ((m[0] // BR) + i) % CB)),
            smem((6, L)),
            smem((1, L)),
            smem((1, 4)),
            smem((L, 1)),
        ],
        scratch_shapes=[
            pltpu.VMEM((BR, Mk), jnp.int32),
            pltpu.SMEM((5, max(L, 3)), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_steady_kernel, BR, cap, L, pconsts, local),
        name="raft_step",
        out_shape=[
            jax.ShapeDtypeStruct((cap, M), log_payload.dtype),
            jax.ShapeDtypeStruct((TL, cap), log_term.dtype),
            jax.ShapeDtypeStruct((6, L), jnp.int32),
            jax.ShapeDtypeStruct((1, L), jnp.int32),
            jax.ShapeDtypeStruct((1, 4), jnp.int32),
            jax.ShapeDtypeStruct((L, 1), jnp.int32),
        ],
        grid_spec=grid_spec,
        # buf_p, buf_t written in place (inputs after the scalar-prefetch
        # arg: cnt, prev_col, params, vecs, masks, win, buf_p=#7, buf_t=#8)
        input_output_aliases={7: 0, 8: 1},
        interpret=interpret,
    )(s, cnt, prev_col, params, vecs, masks, win, log_payload, log_term)


def _pack(state: ReplicaState) -> jax.Array:
    return jnp.stack([
        state.term, state.voted_for, state.last_index, state.commit_index,
        state.match_index, state.match_term,
    ]).astype(jnp.int32)


def _unpack(vecs, log_term, log_payload) -> ReplicaState:
    return ReplicaState(
        term=vecs[_VT], voted_for=vecs[_VV], last_index=vecs[_VL],
        commit_index=vecs[_VC], match_index=vecs[_VMI],
        match_term=vecs[_VMT], log_term=log_term, log_payload=log_payload,
    )


def _params_and_masks(leader, leader_term, term_floor, repair_floor,
                      floor_prev_term, alive, slow, member, commit_quorum,
                      L, ec=False, my=None):
    if member is None:
        quorum = jnp.int32(
            commit_quorum if commit_quorum is not None else L // 2 + 1
        )
        ackm = alive
    else:
        quorum = jnp.sum(member.astype(jnp.int32)) // 2 + 1
        if ec and commit_quorum is not None:
            # EC durability floor only (mirrors core.step.replicate_step's
            # member branch): the static k+margin quorum must hold no
            # matter how far membership shrinks. For non-EC the member
            # majority alone governs — clamping to the INITIAL majority
            # here would wedge a legitimately shrunk cluster (e.g. 5->2
            # members needing 3 acks from 2 rows) and diverge from the
            # general XLA path.
            quorum = jnp.maximum(quorum, jnp.int32(commit_quorum))
        ackm = alive & member
    params = jnp.stack([
        jnp.int32(leader), jnp.int32(leader_term), jnp.int32(term_floor),
        jnp.int32(repair_floor), jnp.int32(floor_prev_term), quorum,
        jnp.int32(-1 if my is None else my),
    ])[None, :]
    masks = jnp.stack([alive, slow, ackm]).astype(jnp.int32)
    return params, masks


def _mk_info(match_o, scal_o):
    from raft_tpu.core.step import RepInfo

    return RepInfo(
        commit_index=scal_o[0, 0], match=match_o[0], max_term=scal_o[0, 1],
        repair_start=jnp.int32(0), frontier_len=scal_o[0, 2],
    )


@functools.partial(
    jax.jit,
    static_argnames=("commit_quorum", "ec", "interpret"),
    donate_argnums=(0,),
)
def steady_replicate_step_tpu(
    state: ReplicaState,
    client_payload: jax.Array,      # i32[B, L*W] folded batch
    client_count: jax.Array,        # i32[]
    leader: jax.Array,              # i32[]
    leader_term: jax.Array,         # i32[]
    alive: jax.Array,               # bool[L]
    slow: jax.Array,                # bool[L]
    floor_prev_term: jax.Array,     # i32[]
    repair_floor: jax.Array,        # i32[]
    member: jax.Array | None,       # bool[L] | None
    term_floor: jax.Array,          # i32[] first index of leader's term
    commit_quorum: int | None = None,
    ec: bool = False,               # STATIC: EC cluster — the commit
    #                                 quorum is the k+margin durability
    #                                 floor and must clamp the member
    #                                 majority (see _params_and_masks)
    interpret: bool = False,
):
    """One steady-state replication step, resident layout, one pallas_call.

    Semantics identical to ``core.step.replicate_step(repair=False)``
    given a correct ``term_floor`` (see module doc); returns the same
    ``(ReplicaState, RepInfo)``.
    """
    cap = state.capacity
    L = state.term.shape[0]
    vecs = _pack(state)
    params, masks = _params_and_masks(
        leader, leader_term, term_floor, repair_floor, floor_prev_term,
        alive, slow, member, commit_quorum, L, ec=ec,
    )
    s, prev_col = _start_slot_and_prev(vecs, state.log_term, leader, cap, L)
    cnt = jnp.int32(client_count).reshape(1, 1)
    log_payload, log_term, vecs_o, match_o, scal_o, _nextp = _invoke(
        s, cnt, prev_col, params, vecs, masks, client_payload,
        state.log_payload, state.log_term, interpret,
    )
    return _unpack(vecs_o, log_term, log_payload), _mk_info(match_o, scal_o)


def steady_scan_replicate_tpu(
    state: ReplicaState,
    payloads: jax.Array,            # i32[T, B, L*W] — or any xs pytree
    #                                 when ``mk_payload`` is given
    counts: jax.Array,              # i32[T]
    leader: jax.Array,
    leader_term: jax.Array,
    alive: jax.Array,
    slow: jax.Array,
    floor_prev_term: jax.Array,
    repair_floor: jax.Array,
    member: jax.Array | None,
    term_floor: jax.Array,
    commit_quorum: int | None = None,
    ec: bool = False,               # STATIC: see steady_replicate_step_tpu
    interpret: bool = False,
    mk_payload=None,                # optional per-step window factory:
    #                                 win = mk_payload(xs_elem) inside the
    #                                 loop body (bench.py carries payload
    #                                 work in the scan so XLA cannot hoist
    #                                 it; the engine passes real batches)
    stack_infos: bool = True,       # False: return only the LAST step's
    #                                 RepInfo (carried, no per-step ys
    #                                 stacking — the stacking DUS costs
    #                                 ~0.6 us/step; bench asserts only the
    #                                 final commit)
    ec_consts=None,                 # u8[rows-k, k, 8] parity-matrix
    #                                 bit-decomposition table: the windows
    #                                 carry only the k DATA lane blocks
    #                                 (i32[B, k*W]) and the kernel encodes
    #                                 the parity lanes in the merge pass —
    #                                 encode + ring write in one VMEM
    #                                 traversal (ec.kernels._bit_consts of
    #                                 RSCode(rows, k).parity_matrix)
):
    """T fused steady steps with the packed (6, L) state-vector carry —
    pack/unpack and param/mask setup happen once per scan, not per step."""
    cap = state.capacity
    L = state.term.shape[0]
    vecs0 = _pack(state)
    params, masks = _params_and_masks(
        leader, leader_term, term_floor, repair_floor, floor_prev_term,
        alive, slow, member, commit_quorum, L,
        # in-kernel parity encoding (ec_consts) is only ever an EC
        # configuration; engine EC chunks instead arrive pre-encoded
        # (full-lane windows, ec_consts=None) and signal via ec=True
        ec=ec or ec_consts is not None,
    )

    def body(carry, xs):
        vecs, log_term, log_payload, s, prev_col = carry[:5]
        win, cnt = xs
        if mk_payload is not None:
            win = mk_payload(win)
        log_payload, log_term, vecs, match_o, scal_o, next_prev = _invoke(
            s, jnp.int32(cnt).reshape(1, 1), prev_col, params, vecs, masks,
            win, log_payload, log_term, interpret, pconsts=ec_consts,
        )
        info = _mk_info(match_o, scal_o)
        # the kernel hands the next iteration its window start slot and
        # prev-term column — no host-graph op between iterations depends
        # on the previous kernel's big outputs
        carry = (vecs, log_term, log_payload, scal_o[0, 3][None], next_prev)
        if stack_infos:
            return carry, info
        return carry + (info,), None   # last info rides the carry instead

    s0, prev0 = _start_slot_and_prev(vecs0, state.log_term, leader, cap, L)
    carry0 = (vecs0, state.log_term, state.log_payload, s0, prev0)
    if not stack_infos:
        carry0 = carry0 + (_mk_info(
            jnp.zeros((1, L), jnp.int32), jnp.zeros((1, 4), jnp.int32)
        ),)
    final, infos = jax.lax.scan(body, carry0, (payloads, counts))
    state = _unpack(final[0], final[1], final[2])
    return state, (infos if stack_infos else final[5])
