"""Replica-axis communication primitives.

The protocol kernels in ``core.step`` are written once against this tiny
interface and run in two placements:

- ``SingleDeviceComm`` — the whole replica-major state lives on one device
  (the replica axis is an ordinary batch axis); "collectives" are plain
  reductions/indexing. This is how the benchmark runs on a single TPU chip,
  and how ``vmap``-style CI tests run.
- ``MeshComm`` — the state is sharded one replica row per device over a
  ``jax.sharding.Mesh`` axis (ICI), and the same operations lower to XLA
  collectives (``all_gather``) inside ``shard_map``.

This is the TPU-native answer to the reference's transport layer: there, a
"send" is a raw write into a peer's Go channel and a "reply" is a blocking
read on the sender's own channel with no correlation id (main.go:344, 373,
131 — SURVEY.md §2 "transport semantics"). Collectives correlate request and
response by construction, so the reference's misattribution hazard (its
main.go:242 bug class) cannot exist here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """The one ``jax.shard_map`` call every mesh program build goes
    through (``transport.tpu_mesh`` and, via it, ``transport.multihost``
    and ``transport.group_mesh``), so the keyword set lives in one
    place."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


class Comm:
    """Interface. L = replica rows held locally, R = cluster size."""

    n_replicas: int

    def replica_ids(self) -> jax.Array:
        """Global replica id of each local row — i32[L]."""
        raise NotImplementedError

    def local(self, x: jax.Array) -> jax.Array:
        """Rows of a replicated [R, ...] vector held locally -> [L, ...].
        Identity on the resident layout (avoids the generic gather XLA
        emits for ``x[replica_ids()]`` — ~0.35 us per call on v5e)."""
        raise NotImplementedError

    def all_gather(self, x: jax.Array) -> jax.Array:
        """[L, ...] per-replica values -> full [R, ...] on every participant."""
        raise NotImplementedError

    def select_row(self, x: jax.Array, idx) -> jax.Array:
        """Broadcast one replica's row to all: [L, ...] -> [...] of row ``idx``."""
        raise NotImplementedError

    def leader_cols(self, win: jax.Array, leader: jax.Array, w: int) -> jax.Array:
        """Replace every replica's lane block with the leader's.

        ``win``: [B, L*w] folded payload window (core.state layout); result
        has the leader's w lanes in every local block — the payload of the
        reference's leader->peer full/suffix sends (main.go:344-361), as a
        collective over the lane axis.
        """
        raise NotImplementedError


class SingleDeviceComm(Comm):
    """All R replica rows resident on one device (L == R)."""

    def __init__(self, n_replicas: int):
        self.n_replicas = n_replicas

    def replica_ids(self) -> jax.Array:
        return jnp.arange(self.n_replicas, dtype=jnp.int32)

    def local(self, x: jax.Array) -> jax.Array:
        return x

    def all_gather(self, x: jax.Array) -> jax.Array:
        return x

    def select_row(self, x: jax.Array, idx) -> jax.Array:
        return x[idx]

    def leader_cols(self, win: jax.Array, leader: jax.Array, w: int) -> jax.Array:
        block = lax.dynamic_slice(
            win, (jnp.int32(0), leader * w), (win.shape[0], w)
        )
        return jnp.tile(block, (1, self.n_replicas))


class MeshComm(Comm):
    """One replica row per device along mesh axis ``axis`` (L == 1).

    Only meaningful inside ``shard_map`` over that axis; ``all_gather`` rides
    ICI (or the virtual-device loopback in CPU tests).
    """

    def __init__(self, n_replicas: int, axis: str = "replica"):
        self.n_replicas = n_replicas
        self.axis = axis

    def replica_ids(self) -> jax.Array:
        return lax.axis_index(self.axis).astype(jnp.int32)[None]

    def local(self, x: jax.Array) -> jax.Array:
        return lax.dynamic_slice_in_dim(x, lax.axis_index(self.axis), 1)

    def all_gather(self, x: jax.Array) -> jax.Array:
        return lax.all_gather(x, self.axis, tiled=True)

    def select_row(self, x: jax.Array, idx) -> jax.Array:
        return lax.all_gather(x, self.axis, tiled=True)[idx]

    def leader_cols(self, win: jax.Array, leader: jax.Array, w: int) -> jax.Array:
        # gather all replicas' lane blocks over ICI, keep the leader's
        # (w == the local lane count: L == 1 rows per device)
        g = lax.all_gather(win, self.axis, axis=1, tiled=True)
        return lax.dynamic_slice(
            g, (jnp.int32(0), leader * w), (win.shape[0], w)
        )
