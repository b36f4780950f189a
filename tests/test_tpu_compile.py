"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e (``jax.experimental.topologies``): no chip attached, nothing runs.

What interpret mode cannot show, the chip's compiler refuses here: block
shapes off the tiling, more VMEM than a kernel may use, programs that do
not fit the device. Each test compiles one kernel or program at the size
``chip_smoke.py`` drives it and asserts the Mosaic kernel is in the
compiled module (``tpu_custom_call``), under the stable name its
``pallas_call`` gives it (what a profiler trace shows for the op).

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and xdist workers all
import this file (on-chip-measurement guide §2).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from raft_tpu.config import RaftConfig
from raft_tpu.core.state import init_state

NS = dict(n_replicas=3, entry_bytes=256, batch_size=1024, transport="single")
EC = dict(n_replicas=5, rs_k=3, rs_m=2, entry_bytes=264, batch_size=1024,
          transport="single")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_cache):
    """Steer the CPU-default process onto the Mosaic path: kernel-eligible
    shapes take the Pallas kernels, compiled rather than interpreted."""
    from raft_tpu.core import ring
    from raft_tpu.transport import tpu_mesh

    monkeypatch.setattr(ring, "_force_interpret", True)
    monkeypatch.setattr(ring, "pallas_interpret", lambda: False)
    # described devices share ids with this process's CPU devices: keep
    # their meshes and programs out of the process-wide caches
    monkeypatch.setattr(tpu_mesh, "_MESHES", {})
    monkeypatch.setattr(tpu_mesh, "_PROGRAMS", {})


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(cfg, sharding):
    shapes = jax.eval_shape(functools.partial(init_state, cfg))
    if not isinstance(sharding, type(shapes)):
        sharding = jax.tree.map(lambda _: sharding, shapes)
    return jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s),
                        shapes, sharding)


def _scalars(sharding, n):
    return [_sds((), jnp.int32, sharding) for _ in range(n)]


def _assert_kernel(lowered, *names):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), name


def _step_args(cfg, sh):
    """Operands after ``state`` of the fused steady step, resident
    layout: payload, count, leader, term, alive, slow, fpt, floor,
    member, term_floor."""
    c, leader, term, fpt, rf, tf = _scalars(sh, 6)
    vec = _sds((cfg.rows,), jnp.bool_, sh)
    return c, leader, term, vec, vec, fpt, rf, None, tf


class TestSingleChipKernels:
    def test_steady_step_ring_2e20(self, mosaic, one_chip):
        from raft_tpu.core.step_pallas import steady_replicate_step_tpu

        cfg = RaftConfig(log_capacity=1 << 20, **NS)
        win = _sds((cfg.batch_size, cfg.rows * cfg.shard_words), jnp.int32,
                   one_chip)
        _assert_kernel(steady_replicate_step_tpu.lower(
            _state(cfg, one_chip), win, *_step_args(cfg, one_chip),
            commit_quorum=cfg.commit_quorum, interpret=False,
        ), "raft_step")

    @pytest.mark.parametrize("cap,T", [
        (1 << 20, 32),               # chip_smoke's north-star ring
        (1 << 17, 128),              # one whole lap of a 2^17 ring
    ])
    def test_lap_scan(self, mosaic, one_chip, cap, T):
        """The fused scan ``submit_pipelined`` runs for a multi-batch
        chunk, with its per-step infos stacked as the engine reads them."""
        from raft_tpu.core.step_pallas import steady_scan_replicate_tpu

        cfg = RaftConfig(log_capacity=cap, **NS)
        wins = _sds((T, cfg.batch_size, cfg.rows * cfg.shard_words),
                    jnp.int32, one_chip)
        counts = _sds((T,), jnp.int32, one_chip)
        _, leader, term, vec, _, fpt, rf, _, tf = _step_args(cfg, one_chip)
        fn = jax.jit(functools.partial(
            steady_scan_replicate_tpu, commit_quorum=cfg.commit_quorum,
            interpret=False,
        ))
        _assert_kernel(fn.lower(
            _state(cfg, one_chip), wins, counts, leader, term, vec, vec,
            fpt, rf, None, tf,
        ), "raft_step")

    def test_ring_write_2e20(self, mosaic, one_chip):
        from raft_tpu.core.ring_pallas import write_window_cols_tpu

        cfg = RaftConfig(log_capacity=1 << 20, **NS)
        M = cfg.rows * cfg.shard_words
        s, count = _scalars(one_chip, 2)
        _assert_kernel(write_window_cols_tpu.lower(
            _sds((cfg.log_capacity, M), jnp.int32, one_chip),
            _sds((cfg.batch_size, M), jnp.int32, one_chip), s, count,
            _sds((M,), jnp.bool_, one_chip), interpret=False,
        ), "ring_write")

    def test_ring_write_both_2e20(self, mosaic, one_chip):
        """The fused payload + term ring write with the conflict check
        (``core.step``'s ingest at north-star width)."""
        from raft_tpu.core.ring_pallas import write_window_both_tpu

        cfg = RaftConfig(log_capacity=1 << 20, **NS)
        C, B, L = cfg.log_capacity, cfg.batch_size, cfg.rows
        M = L * cfg.shard_words
        s, count, ws = _scalars(one_chip, 3)
        _assert_kernel(write_window_both_tpu.lower(
            _sds((C, M), jnp.int32, one_chip),
            _sds((L, C), jnp.int32, one_chip),
            _sds((B, M), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip), s, count, ws,
            _sds((L,), jnp.bool_, one_chip),
            _sds((L,), jnp.int32, one_chip), interpret=False,
        ), "ring_write_both")

    def test_ec_fused_steady_step(self, mosaic, one_chip):
        from raft_tpu.core.step_pallas import steady_scan_replicate_tpu
        from raft_tpu.ec.kernels import parity_consts

        cfg = RaftConfig(log_capacity=1 << 17, **EC)
        T = 4
        wins = _sds((T, cfg.batch_size, cfg.rs_k * cfg.shard_words),
                    jnp.int32, one_chip)
        counts = _sds((T,), jnp.int32, one_chip)
        _, leader, term, vec, _, fpt, rf, _, tf = _step_args(cfg, one_chip)
        fn = jax.jit(functools.partial(
            steady_scan_replicate_tpu, commit_quorum=cfg.commit_quorum,
            interpret=False, stack_infos=False,
            ec_consts=parity_consts(cfg.rows, cfg.rs_k),
        ), donate_argnums=(0,))
        _assert_kernel(fn.lower(
            _state(cfg, one_chip), wins, counts, leader, term, vec, vec,
            fpt, rf, None, tf,
        ), "raft_step")

    @pytest.mark.parametrize("rows", [1024, 1 << 17])
    def test_ec_encode_fold(self, no_cache, one_chip, rows):
        """The engine's ingest encode: one batch, and one whole ring lap
        as submit_pipelined hands it over."""
        from raft_tpu.ec.kernels import _encode_fold_pallas, _parity_consts_key

        data = _sds((rows, 264), jnp.uint8, one_chip)
        _assert_kernel(_encode_fold_pallas.lower(
            3, 2, _parity_consts_key(5, 3), data, False,
        ), "rs_encode_fold")

    def test_ec_encode(self, no_cache, one_chip):
        from raft_tpu.ec.kernels import encode_pallas
        from raft_tpu.ec.rs import RSCode

        data = _sds((1024, 264), jnp.uint8, one_chip)
        fn = jax.jit(lambda d: encode_pallas(RSCode(5, 3), d,
                                             interpret=False))
        _assert_kernel(fn.lower(data), "rs_parity")

    @pytest.mark.parametrize("rows", [1024, 1 << 17])
    def test_ec_decode(self, no_cache, one_chip, rows):
        """The reconstruction read from a non-systematic row subset: one
        batch window, and a whole 2^17 ring lap (chip_smoke phase d)."""
        from raft_tpu.ec.kernels import decode_pallas
        from raft_tpu.ec.rs import RSCode

        shards = _sds((3, rows, 88), jnp.uint8, one_chip)
        fn = jax.jit(lambda s: decode_pallas(RSCode(5, 3), s, [1, 2, 3],
                                             interpret=False))
        _assert_kernel(fn.lower(shards), "rs_parity")


class TestMeshPrograms:
    """The default ``tpu_mesh`` transport's shard_map programs over three
    described chips (one replica row each) at 2^15."""

    @pytest.fixture
    def mesh_t(self, mosaic, topo):
        from raft_tpu.transport.tpu_mesh import TpuMeshTransport

        cfg = RaftConfig(n_replicas=3, entry_bytes=256, batch_size=1024,
                         log_capacity=1 << 15, transport="tpu_mesh")
        return TpuMeshTransport(cfg, topo.devices[:3])

    def _args(self, t, win_shape):
        rep = NamedSharding(t.mesh, jax.sharding.PartitionSpec())
        state = _state(t.cfg, jax.tree.map(
            lambda spec: NamedSharding(t.mesh, spec), t._state_specs))
        win = _sds(win_shape, jnp.int32,
                   NamedSharding(t.mesh, jax.sharding.PartitionSpec(
                       *([None] * (len(win_shape) - 1)), t._lanes)))
        c, leader, term, fpt, rf, tf = _scalars(rep, 6)
        vec = _sds((t.cfg.rows,), jnp.bool_, rep)
        return state, win, c, leader, term, vec, vec, fpt, rf, tf

    def test_mesh_replicate(self, mesh_t):
        t = mesh_t
        W = t.cfg.rows * t.cfg.shard_words
        state, win, c, leader, term, a, s, fpt, rf, tf = self._args(
            t, (t.cfg.batch_size, W))
        prog = t._fused_program("replicate", False).__wrapped__
        _assert_kernel(prog.lower(state, win, c, leader, term, a, s, fpt,
                                  rf, tf))

    def test_mesh_lap_scan(self, mesh_t):
        """One whole lap of the 2^15 ring as the repair-free fused scan
        (``replicate_many``), the program a mesh chunk runs."""
        t = mesh_t
        T = t.cfg.log_capacity // t.cfg.batch_size
        W = t.cfg.rows * t.cfg.shard_words
        state, wins, _, leader, term, a, s, fpt, rf, tf = self._args(
            t, (T, t.cfg.batch_size, W))
        counts = _sds((T,), jnp.int32,
                      NamedSharding(t.mesh, jax.sharding.PartitionSpec()))
        prog = t._fused_program("replicate_many", False).__wrapped__
        _assert_kernel(prog.lower(state, wins, counts, leader, term, a, s,
                                  fpt, rf, tf))


def test_ec_kernels_tile_rows_interpret():
    """The EC kernels' row grid (several blocks) matches the XLA
    bit-sliced formulation byte for byte in interpret mode."""
    from raft_tpu.ec.kernels import (
        _encode_fold_pallas, _parity_consts_key, decode_bitwise_xla,
        decode_pallas, encode_bitwise_xla, encode_pallas, fold_shards_device,
    )
    from raft_tpu.ec.rs import RSCode

    code = RSCode(5, 3)
    rng = np.random.default_rng(5)
    data = jnp.asarray(rng.integers(0, 256, (2048 + 64, 264), np.uint8))
    shards = encode_bitwise_xla(code, data)
    np.testing.assert_array_equal(
        np.asarray(encode_pallas(code, data, interpret=True)),
        np.asarray(shards))
    np.testing.assert_array_equal(
        np.asarray(_encode_fold_pallas(3, 2, _parity_consts_key(5, 3),
                                       data, True)),
        np.asarray(fold_shards_device(shards)))
    rows = [0, 2, 4]
    np.testing.assert_array_equal(
        np.asarray(decode_pallas(code, shards[np.array(rows)], rows,
                                 interpret=True)),
        np.asarray(decode_bitwise_xla(code, shards[np.array(rows)], rows)))
