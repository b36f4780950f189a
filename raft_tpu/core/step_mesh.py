"""Per-device fused steady kernels for the mesh transport.

Round-4 verdict #1: the resident fused kernels (``core.step_pallas``) only
ran when every replica row shared one chip; the mesh — the shape consensus
actually deploys on — fell back to the general XLA formulation. This
module brings the fused data path to the mesh with a TPU-native split:

**Replicated scalar plane, local data plane.** Inside ``shard_map`` each
device holds ONE replica row's ring (payload ``(C, W)`` lanes, terms
``(1, C)``) plus that row's six protocol scalars. One launch-time
``all_gather`` moves every row's packed scalars (6 ints each) and the
prev-term column to every device; from there each device runs the SAME
SMEM scalar core as the resident kernel — simulating ALL R rows'
accounting (accept sets, match vector, quorum commit, term adoption)
redundantly, which is replicated SPMD work on sub-microsecond operands —
while its VMEM traffic touches only the local row's lanes. A T-step
flight therefore needs exactly TWO small collectives total (the packed
gather + the prev column), not O(T) rounds: the reference's per-step
ack/commit message exchange (main.go:344-391) becomes launch-time state
exchange plus deterministic replicated replay.

**Why no per-step communication is sound.** The steady program's cross-row
observables are closed-form in the launch state and the (flight-frozen)
fault masks, given two invariants the engine maintains:

1. *No follower holds a current-term entry beyond the leader's tail* —
   the leader appends before replicating, truncation clamps every row,
   and two leaders never share a term. Hence an accepting row's window
   overlap always conflicts (old-term entries) and its new tail is
   exactly the window end; a longer "consistent suffix" cannot exist.
2. *Non-accepting rows stay non-accepting for the flight* — a row that
   rejects window t has, at window t+1's prev slot, either a too-short
   log or a non-current term (by invariant 1), so its accept boolean
   stays False; accepting rows' prev is the ``lterm`` they just wrote.

The §5.3 conflict bit and the next-prev stash — the only places the
resident kernel reads OTHER rows' ring content — are replaced by those
closed forms (``local=True`` in ``core.step_pallas``'s kernel body; the
data-plane geometry, merge, and quorum arithmetic are the very same
code). The engine-level differential and chaos suites pin the invariants;
``tests/test_step_mesh.py`` pins this path byte-identical to the general
mesh formulation.

EC note: the engine pre-encodes RS shards into full-lane folded windows
before any transport call, so each device's local window block IS its
shard — the mesh kernels never need the in-kernel parity encode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.state import ReplicaState
from raft_tpu.core.step_pallas import (
    _VC,
    _VL,
    _VMI,
    _VMT,
    _VT,
    _VV,
    _frontier_slots,
    _invoke,
    _mk_info,
    _params_and_masks,
)

# trace-time marker: the most recent fused-mesh entry point traced, so
# integration tests and the multichip dryrun can assert the mesh program
# actually routed through this module (a silent fallback to the general
# formulation was round 4's headline gap)
LAST_DISPATCH: str | None = None


def _local_vec(state: ReplicaState) -> jax.Array:
    """The local row's packed scalar six-vector (shape (6,))."""
    return jnp.stack([
        state.term[0], state.voted_for[0], state.last_index[0],
        state.commit_index[0], state.match_index[0], state.match_term[0],
    ]).astype(jnp.int32)


def _gather_plane(state: ReplicaState, leader, axis: str, cap: int):
    """The two launch collectives: every row's packed scalars -> (6, R)
    and every row's prev-term (at the slot before the leader's frontier)
    -> (R, 1); plus the window start slot, shaped like the resident
    ``_start_slot_and_prev``."""
    vecs = lax.all_gather(_local_vec(state), axis).T          # (6, R)
    s, prev_slot = _frontier_slots(vecs[_VL, leader], cap)
    own_prev = lax.dynamic_slice(
        state.log_term, (jnp.int32(0), prev_slot), (1, 1)
    )[0, 0].astype(jnp.int32)
    prev_col = lax.all_gather(own_prev, axis)[:, None]        # (R, 1)
    return vecs, prev_col, s


def _unpack_local(axis: str, vecs_o, log_term, log_payload) -> ReplicaState:
    """Slice the local row's scalars back out of the replicated (6, R)
    result; the ring buffers are already local."""
    my = lax.axis_index(axis)
    own = lax.dynamic_slice(vecs_o, (jnp.int32(0), my), (6, 1))
    return ReplicaState(
        term=own[_VT], voted_for=own[_VV], last_index=own[_VL],
        commit_index=own[_VC], match_index=own[_VMI],
        match_term=own[_VMT], log_term=log_term, log_payload=log_payload,
    )


def _plane_and_params(state, leader, leader_term, term_floor, repair_floor,
                      floor_prev_term, alive, slow, member, commit_quorum,
                      ec, axis):
    cap = state.capacity
    R = alive.shape[0]
    leader = jnp.int32(leader)
    vecs, prev_col, s = _gather_plane(state, leader, axis, cap)
    params, masks = _params_and_masks(
        leader, leader_term, term_floor, repair_floor, floor_prev_term,
        alive, slow, member, commit_quorum, R, ec=ec,
        my=lax.axis_index(axis),
    )
    return vecs, prev_col, s, params, masks


def mesh_replicate_step(
    axis: str,
    state: ReplicaState,            # LOCAL row (inside shard_map)
    client_payload: jax.Array,      # i32[B, W] local lane block
    client_count: jax.Array,
    leader: jax.Array,
    leader_term: jax.Array,
    alive: jax.Array,               # bool[R] replicated
    slow: jax.Array,
    floor_prev_term: jax.Array,
    repair_floor: jax.Array,
    member: jax.Array | None,
    term_floor: jax.Array,
    commit_quorum: int | None = None,
    ec: bool = False,
    interpret: bool = False,
):
    """One fused steady step on the mesh layout — semantics identical to
    the general ``core.step.replicate_step(repair=False)`` under
    ``shard_map`` (pinned by tests/test_step_mesh.py), with the
    collective profile reduced to the two launch gathers."""
    global LAST_DISPATCH
    LAST_DISPATCH = "step"
    vecs, prev_col, s, params, masks = _plane_and_params(
        state, leader, leader_term, term_floor, repair_floor,
        floor_prev_term, alive, slow, member, commit_quorum, ec, axis,
    )
    cnt = jnp.int32(client_count).reshape(1, 1)
    log_payload, log_term, vecs_o, match_o, scal_o, _nextp = _invoke(
        s, cnt, prev_col, params, vecs, masks, client_payload,
        state.log_payload, state.log_term, interpret, local=True,
    )
    return (
        _unpack_local(axis, vecs_o, log_term, log_payload),
        _mk_info(match_o, scal_o),
    )


def mesh_scan_replicate(
    axis: str,
    state: ReplicaState,
    payloads: jax.Array,            # i32[T, B, W] local lane blocks
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
    ec: bool = False,
    interpret: bool = False,
    stack_infos: bool = True,
):
    """T fused steps, ONE gather: the packed (6, R) scalar plane rides
    the scan carry (replicated), the kernel hands each next iteration its
    start slot and closed-form prev column — zero collectives inside the
    loop."""
    global LAST_DISPATCH
    LAST_DISPATCH = "scan"
    vecs0, prev0, s0, params, masks = _plane_and_params(
        state, leader, leader_term, term_floor, repair_floor,
        floor_prev_term, alive, slow, member, commit_quorum, ec, axis,
    )
    R = vecs0.shape[1]

    def body(carry, xs):
        vecs, lt, lp, s, prev_col = carry[:5]
        win, cnt = xs
        lp, lt, vecs, match_o, scal_o, next_prev = _invoke(
            s, jnp.int32(cnt).reshape(1, 1), prev_col, params, vecs,
            masks, win, lp, lt, interpret, local=True,
        )
        info = _mk_info(match_o, scal_o)
        carry = (vecs, lt, lp, scal_o[0, 3][None], next_prev)
        if stack_infos:
            return carry, info
        return carry + (info,), None

    carry0 = (vecs0, state.log_term, state.log_payload, s0, prev0)
    if not stack_infos:
        carry0 = carry0 + (_mk_info(
            jnp.zeros((1, R), jnp.int32), jnp.zeros((1, 4), jnp.int32)
        ),)
    final, infos = lax.scan(body, carry0, (payloads, counts))
    state = _unpack_local(axis, final[0], final[1], final[2])
    return state, (infos if stack_infos else final[5])
