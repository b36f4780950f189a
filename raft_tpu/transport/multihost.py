"""Multi-host bootstrap: replica rows placed across failure domains.

The reference's "network" is a map of Go channels inside one process
(main.go:12) — all three replicas die together, which defeats the point of
consensus. On a TPU pod the failure domains are hosts/slices, so the mesh
must be built the other way around from a training job's: the **replica
axis spans processes** (each replica's state machine lives on a different
host's chips, AppendEntries/vote collectives ride DCN between slices and
ICI inside one), while the optional **payload-shard axis stays inside a
process** (byte-slices of one replica's log move over local ICI only).

Usage on each host of a pod (standard JAX multi-process setup):

    from raft_tpu.transport.multihost import (
        initialize_multihost, multihost_transport,
    )
    initialize_multihost(coordinator_address="host0:1234",
                         num_processes=N, process_id=i)   # no-op if N == 1
    t = multihost_transport(cfg)       # replica axis across processes
    eng = RaftEngine(cfg, t)           # every process runs the same program

The protocol DATA PLANE (vote rounds, replication, quorum commit — all
`shard_map` collectives whose info outputs are replicated) is fully
multi-process, and so is the FULL ENGINE: every process runs
``RaftEngine`` as a **mirrored deterministic event loop** — same config
seed, same timer heap, same decisions — so all processes issue identical
collective launches, which makes host reads of sharded rows legal as
collectives too (``TpuMeshTransport.fetch``: a jit identity resharded to
fully-replicated). CI proves both layers with real two-OS-process
clusters over the JAX distributed runtime (tests/test_multiprocess.py):
transport-level steps, and the complete engine driving client traffic
and a leadership change end-to-end with byte-identical committed logs on
every process. Mirroring is the control-plane replication strategy: a
host crash kills one replica row's device shards, not the cluster's only
brain — any surviving process still holds the full control state.
Placement rules are additionally covered by fake-fabric unit tests and
the single-process virtual mesh.

Surviving a real process death (what re-formation requires)
-----------------------------------------------------------
``tests/test_multiprocess.py::test_process_death_survivor_reforms`` kills
one of two OS processes with SIGKILL mid-traffic and asserts the survivor
keeps committing; ``test_three_process_reformation_and_rejoin`` runs the
FULL elastic loop at N=3 — the surviving majority agrees on who is left,
derives a new coordinator, re-forms, keeps committing, and the killed
process later rejoins and snapshot-heals back to full strength. The
survivor-agreement/epoch machinery lives in ``transport.reform``
(heartbeats, deterministic coordinator derivation, max-watermark
checkpoint election, write-once epoch publication, join requests). The
recovery contract, honestly stated:

1. **Detection.** A fixed JAX mesh gives no failure notification for a
   non-leader peer: the survivor's next collective simply stalls.
   Detection is therefore a *progress watchdog* — the mirrored loops
   commit in lockstep, so "no committed round for T seconds" is the
   peer-death signal. T must exceed the longest legitimate stall
   (compiles, checkpoint writes). Death of the runtime COORDINATOR is
   detected faster and harder: the coordination service fast-fails every
   surviving worker (an uncatchable LOG(FATAL)), so each host runs a
   tiny supervisor (the k8s/systemd pattern) that treats that exit as
   the detection signal and restarts the worker into the re-formation
   path.
2. **Re-formation is a restart, not a live mesh shrink.** XLA backends
   pin the process set at ``jax.distributed.initialize``; a survivor
   cannot drop a dead peer from a live mesh. It re-execs itself (or is
   restarted by its supervisor — the same thing k8s does), initializes a
   fresh runtime over the processes that remain, and rebuilds the
   transport over the surviving devices.
3. **State comes from stable storage, not device memory.** The dead
   host's replica-row shards are gone. Because checkpoints are
   cluster-wide (mirrored control planes archive every commit) and every
   process writes its own vote WAL, ANY surviving process can restore
   the full cluster: rows whose devices died restart from their last
   durable state — exactly Raft's crash-restart model — and the repair
   window / snapshot install heals them forward. The WAL overlay
   guarantees no restored row regresses below a term it acted in (no
   double vote).
4. **Durability fences acks.** An entry is safely acknowledgeable only
   once a checkpoint covering it is on disk; the test's client records
   acks only after ``save_checkpoint`` returns, and recovery asserts the
   acked sequence is a byte-identical prefix of the restored committed
   log. Entries committed after the last checkpoint survive only if some
   surviving process archived them — acks must wait for the fence.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

from raft_tpu.config import RaftConfig
from raft_tpu.obs import blackbox
from raft_tpu.transport.tpu_mesh import TpuMeshTransport


def _enable_cpu_collectives() -> None:
    """On the CPU backend, multi-process XLA computations need a
    cross-process collectives implementation — without one every
    sharded computation dies with ``INVALID_ARGUMENT: Multiprocess
    computations aren't implemented on the CPU backend``. Select Gloo
    (the CI stand-in for DCN) when the knob exists and is unset; a
    TPU/GPU backend ignores it. Must run BEFORE the backend
    initializes, which is why the distributed dial calls it first."""
    try:
        if jax.config._read("jax_cpu_collectives_implementation") in (
            None, "none",
        ):
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo"
            )
    except Exception:
        pass   # a jax line without the knob: nothing to select


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: int = 1,
    process_id: int = 0,
) -> None:
    """Bring up the JAX distributed runtime (a no-op for one process).

    After this, ``jax.devices()`` returns the GLOBAL device list on every
    process — the raw material for ``replica_devices_across_hosts``."""
    if num_processes <= 1:
        return
    _enable_cpu_collectives()
    # write-before-block (obs.blackbox): the distributed runtime dial is
    # the first cross-process rendezvous — a dead coordinator or a
    # firewalled port hangs exactly here, and only the journal says so
    blackbox.mark(
        "distributed_init", coordinator=str(coordinator_address),
        num_processes=num_processes, process_id=process_id,
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    blackbox.mark("distributed_init_done", process_id=process_id)


def replica_devices_across_hosts(
    n_replicas: int,
    payload_shards: int = 1,
    devices: Optional[Sequence] = None,
) -> list:
    """Pick ``n_replicas * payload_shards`` devices so that each replica's
    block comes from a distinct process where possible.

    Grouping key is ``device.process_index`` (the JAX failure domain: one
    host process = one set of locally-attached chips). Placement rules:

    - at least ``n_replicas`` processes: replica i's block is taken wholly
      from process i's devices — every replica in its own failure domain,
      replica-axis collectives ride DCN;
    - fewer processes than replicas: replicas are dealt round-robin over
      the processes (as failure-isolated as the hardware allows), falling
      back to one flat device list for the single-process case.

    Raises when the fabric cannot supply ``payload_shards`` devices from a
    single process for some replica (payload shards must stay on one
    host's ICI — a byte-sliced log row spanning DCN would put the hot
    window path on the slow fabric).
    """
    if devices is None:
        # write-before-block: with no live backend, jax.devices()
        # INITIALIZES one — on a multi-host fabric that waits on every
        # peer process, so a missing peer stalls exactly here
        blackbox.mark(
            "device_enum", n_replicas=n_replicas,
            payload_shards=payload_shards,
        )
    devs = list(devices) if devices is not None else list(jax.devices())
    by_proc: dict = {}
    for d in devs:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    procs = sorted(by_proc)
    if len(procs) == 1:
        flat = by_proc[procs[0]]
        need = n_replicas * payload_shards
        if len(flat) < need:
            raise ValueError(
                f"need {need} devices, single process has {len(flat)}"
            )
        return flat[:need]
    picked = []
    # Greedy block placement: for each replica pick, among the processes
    # that still have a full payload_shards block free, the least-used one
    # (ties broken toward more free devices). This maximizes failure
    # isolation when processes are plentiful AND still places on uneven
    # fabrics (e.g. 2+6 devices over two processes) where a rigid
    # round-robin would dead-end on an exhausted process.
    used = {p: 0 for p in procs}
    cursor = {p: 0 for p in procs}
    for r in range(n_replicas):
        viable = [
            p for p in procs
            if len(by_proc[p]) - cursor[p] >= payload_shards
        ]
        if not viable:
            free = {p: len(by_proc[p]) - cursor[p] for p in procs}
            raise ValueError(
                f"replica {r}: no process has {payload_shards} free "
                f"devices (free per process: {free}); a replica's payload "
                "shards must stay on one process's ICI"
            )
        p = min(
            viable, key=lambda q: (used[q], -(len(by_proc[q]) - cursor[q]))
        )
        at = cursor[p]
        picked.extend(by_proc[p][at:at + payload_shards])
        cursor[p] = at + payload_shards
        used[p] += 1
    return picked


def multihost_transport(
    cfg: RaftConfig,
    payload_shards: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> TpuMeshTransport:
    """A mesh transport whose replica axis spans hosts (see module doc).
    ``devices`` restricts placement to a subset of the global device list
    (default: all of ``jax.devices()``)."""
    shards = cfg.payload_shards if payload_shards is None else payload_shards
    devs = replica_devices_across_hosts(cfg.n_replicas, shards, devices)
    return TpuMeshTransport(cfg, devs, payload_shards=shards)
