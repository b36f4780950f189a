"""Pallas TPU kernel for the RS parity encode — the codec's hot op.

Per-element table gathers (the XLA path in ``rs.py``) don't vectorize on
the VPU; the TPU-native formulation exploits that multiplication by a
*constant* c is GF(2)-linear in the bits of x:

    mul(c, x) = XOR over set bits i of x of mul(c, 2^i)

so one (parity_row, data_row) term is 8 shift/mask/select/XOR elementwise
ops over the whole [B, S/k] tile — pure VPU work with no gathers, and the
per-code constants mul(C[p, j], 2^i) are baked into the kernel at trace
time. RS(5, 3) parity = 2 x 3 x 8 fused elementwise passes.

The same bit-decomposition also backs ``encode_bitwise_xla`` (used on CPU
and as the kernel's reference in tests) — and is what the C++ host codec
vectorizes with SIMD.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from raft_tpu.core import ring
from raft_tpu.ec import gf
from raft_tpu.ec.rs import RSCode


def pallas_interpret() -> bool:
    """``core.ring.pallas_interpret()``, looked up at each call: a caller
    that swaps that function for a while leaves nothing bound here."""
    return ring.pallas_interpret()


def _bit_consts(matrix: np.ndarray) -> np.ndarray:
    """u8[rows, cols, 8]: consts[r, c, i] = mul(matrix[r, c], 1 << i)."""
    rows, cols = matrix.shape
    out = np.zeros((rows, cols, 8), np.uint8)
    for r in range(rows):
        for c in range(cols):
            for i in range(8):
                out[r, c, i] = int(gf.mul(matrix[r, c], np.uint8(1 << i)))
    return out


@lru_cache(maxsize=None)
def _parity_consts_key(n: int, k: int) -> bytes:
    """Per-code parity bit-decomposition constants, computed once — the
    encode paths run every leader tick, the constants never change."""
    return _bit_consts(RSCode(n, k).parity_matrix).tobytes()


def _mul_const_bits(x: jax.Array, consts_rc: np.ndarray) -> jax.Array:
    """mul(c, x) for constant c via bit decomposition; consts_rc = u8[8]."""
    acc = jnp.zeros_like(x)
    for i in range(8):
        if int(consts_rc[i]) == 0:
            continue
        # bit-test + select only: Mosaic legalizes i8 and/cmp/select but not
        # i8 vector muli/shrui (and the mask-select is what the VPU wants)
        bit_set = (x & np.uint8(1 << i)) != 0
        acc = acc ^ jnp.where(
            bit_set, np.uint8(consts_rc[i]), np.uint8(0)
        )
    return acc


def _parity_kernel(consts: np.ndarray, data_ref, out_ref):
    """data_ref: u8[k, bb, Sk] -> out_ref: u8[m, bb, Sk] (one row block)."""
    m, k, _ = consts.shape
    for p in range(m):
        acc = jnp.zeros_like(data_ref[0])
        for j in range(k):
            acc = acc ^ _mul_const_bits(data_ref[j], consts[p, j])
        out_ref[p] = acc


def _row_block(B: int) -> int:
    """Entry rows per grid step: the largest power-of-two block of at
    most 1024 rows (a multiple of the u8 sublane tile, 32) dividing
    ``B``, else the whole batch. Tiling over rows keeps VMEM use at one
    block whatever the window length — a whole ring lap (2^17 x 264 B)
    does not fit VMEM in one piece."""
    for bb in (1024, 512, 256, 128, 64, 32):
        if B % bb == 0:
            return bb
    return B


def _interpret(interpret) -> bool:
    return pallas_interpret() if interpret is None else bool(interpret)


@partial(jax.jit, static_argnums=(0, 1, 2, 4))
def _parity_pallas(k: int, m: int, consts_key, data_sliced: jax.Array,
                   interpret: bool) -> jax.Array:
    """u8[k, B, Sk] data shards -> u8[m, B, Sk] parity shards."""
    consts = np.frombuffer(consts_key, np.uint8).reshape(m, k, 8)
    B, Sk = data_sliced.shape[1], data_sliced.shape[2]
    bb = _row_block(B)
    return pl.pallas_call(
        partial(_parity_kernel, consts),
        name="rs_parity",
        out_shape=jax.ShapeDtypeStruct((m, B, Sk), jnp.uint8),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((k, bb, Sk), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((m, bb, Sk), lambda i: (0, i, 0)),
        interpret=interpret,
    )(data_sliced)


def encode_pallas(code: RSCode, data: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """u8[B, S] entries -> u8[n, B, S/k] shard rows; parity on the TPU
    kernel, data rows by (free) byte-slicing. ``interpret`` defaults to
    ``core.ring.pallas_interpret()``."""
    B, S = data.shape
    d = jnp.moveaxis(data.reshape(B, code.k, S // code.k), 1, 0)
    parity = _parity_pallas(code.k, code.m,
                            _parity_consts_key(code.n, code.k), d,
                            _interpret(interpret))
    return jnp.concatenate([d, parity])


@partial(jax.jit, static_argnums=(0,))
def _encode_bitwise(consts_key_km: tuple, data_sliced: jax.Array) -> jax.Array:
    consts_key, m, k = consts_key_km
    consts = np.frombuffer(consts_key, np.uint8).reshape(m, k, 8)
    outs = []
    for p in range(m):
        acc = jnp.zeros_like(data_sliced[0])
        for j in range(k):
            acc = acc ^ _mul_const_bits(data_sliced[j], consts[p, j])
        outs.append(acc)
    return jnp.stack(outs)


def encode_bitwise_xla(code: RSCode, data: jax.Array) -> jax.Array:
    """Same bit-decomposition math as the Pallas kernel, plain XLA — the
    portable fast path (and the kernel's test reference)."""
    B, S = data.shape
    d = jnp.moveaxis(data.reshape(B, code.k, S // code.k), 1, 0)
    parity = _encode_bitwise(
        (_parity_consts_key(code.n, code.k), code.m, code.k), d
    )
    return jnp.concatenate([d, parity])


def fold_shards_device(shards: jax.Array) -> jax.Array:
    """Device-side fold of shard rows into the log layout: u8[R, B, Sk] ->
    i32[B, R*Wk] (same packing as core.state.fold_rows, no host round trip).

    XLA's bitcast-convert packs the trailing length-4 u8 axis with element 0
    least-significant — the same byte order as numpy's little-endian
    ``view(np.int32)`` host fold (asserted by tests/test_ec.py)."""
    r, b, sk = shards.shape
    x = jnp.swapaxes(shards, 0, 1).reshape(b, r * sk // 4, 4)
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def encode_device(code: RSCode, data: jax.Array) -> jax.Array:
    """Platform-dispatched encode: the Pallas kernel on TPU, the bitwise
    XLA formulation elsewhere (CPU tests / interpret). This is the
    production encode the engine's EC tick calls — the north star names the
    Pallas RS encode as the TPU data path, so TPU must actually run it."""
    if not pallas_interpret():
        return encode_pallas(code, data, interpret=False)
    return encode_bitwise_xla(code, data)


def _parity_cols_kernel(consts, sk: int, data_ref, out_ref):
    """Column-sliced variant: data_ref u8[B, k*Sk] (raw entry bytes, NO
    moveaxis), out_ref u8[B, m*Sk]. Same math as ``_parity_kernel``; the
    shard axis is column blocks, so the kernel consumes the client batch
    in its natural contiguous layout."""
    m, k, _ = consts.shape
    for p in range(m):
        acc = jnp.zeros_like(data_ref[:, :sk])
        for j in range(k):
            acc = acc ^ _mul_const_bits(
                data_ref[:, j * sk:(j + 1) * sk], consts[p, j]
            )
        out_ref[:, p * sk:(p + 1) * sk] = acc


@partial(jax.jit, static_argnums=(0, 1, 2, 4))
def _encode_fold_pallas(k: int, m: int, consts_key, data: jax.Array,
                        interpret: bool) -> jax.Array:
    """u8[B, S] entries -> i32[B, (k+m)*Wk] FOLDED shard layout in one pass.

    The folded layout's data blocks are byte-identical to the input (the
    systematic rows), so only the parity columns are computed (Pallas) and
    the fold is a bitcast + concat — no moveaxis round-trip of the data
    bytes through shard-major layout and back (the copies were ~
    a third of the EC step's encode overhead)."""
    consts = np.frombuffer(consts_key, np.uint8).reshape(m, k, 8)
    B, S = data.shape
    sk = S // k
    bb = _row_block(B)
    parity = pl.pallas_call(
        partial(_parity_cols_kernel, consts, sk),
        name="rs_encode_fold",
        out_shape=jax.ShapeDtypeStruct((B, m * sk), jnp.uint8),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, S), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, m * sk), lambda i: (i, 0)),
        interpret=interpret,
    )(data)

    def to_words(x):
        b, n = x.shape
        return jax.lax.bitcast_convert_type(
            x.reshape(b, n // 4, 4), jnp.int32
        )

    return jnp.concatenate([to_words(data), to_words(parity)], axis=1)


def parity_consts(n: int, k: int) -> np.ndarray:
    """Public view of the parity-matrix bit-decomposition table:
    u8[n-k, k, 8] with ``[p, j, i] = mul(P[p, j], 1 << i)``. Consumed by
    the fused steady kernel's in-kernel encode
    (core.step_pallas ``ec_consts``) and anything else that restates the
    bit-sliced multiply."""
    return np.frombuffer(_parity_consts_key(n, k), np.uint8).reshape(
        n - k, k, 8
    )


def fold_data_lanes(data: jax.Array) -> jax.Array:
    """u8[B, S] raw entry bytes -> i32[B, S/4] — exactly the systematic
    data-lane blocks of the folded layout (``to_words`` of
    ``_encode_fold_pallas``; XLA's bitcast packs element 0
    least-significant, matching the little-endian host fold). The window
    format of the fused kernel's in-kernel-parity mode."""
    b, s = data.shape
    return jax.lax.bitcast_convert_type(
        data.reshape(b, s // 4, 4), jnp.int32
    )


def encode_fold_device(code: RSCode, data: jax.Array) -> jax.Array:
    """Fused encode + fold: u8[B, S] -> i32[B, n*Wk] (the device log
    payload layout). Equals ``fold_shards_device(encode_device(...))``
    exactly (asserted in tests); on TPU it skips the shard-major
    round-trip copies."""
    if not pallas_interpret():
        return _encode_fold_pallas(
            code.k, code.m, _parity_consts_key(code.n, code.k), data, False
        )
    return fold_shards_device(encode_device(code, data))


# --------------------------------------------------------------- decode
# Decoding is the SAME op as the parity encode — apply a constant GF(2^8)
# matrix to k shard rows — just with the inverse (decode) matrix for the
# serving row subset instead of the parity matrix. The per-element LUT
# path (rs._decode_xla) gathers per byte, which doesn't vectorize on the
# VPU; the bit-sliced kernels below are ~50x faster on TPU for a
# batch-sized window (the "reconstruction" read of BASELINE config 3).


@lru_cache(maxsize=None)
def _decode_consts_key(n: int, k: int, rows: tuple) -> bytes:
    """Bit-decomposition constants of decode_matrix(rows), cached per
    (code, serving-row-subset) — there are only C(n, k) of them."""
    return _bit_consts(RSCode(n, k).decode_matrix(list(rows))).tobytes()


def decode_pallas(code: RSCode, shards: jax.Array, rows,
                  interpret: bool | None = None) -> jax.Array:
    """u8[k, B, Sk] shards from ``rows`` -> u8[B, S] decoded entries, on
    the same bit-sliced kernel as the parity encode."""
    rows = tuple(int(r) for r in rows)
    out = _parity_pallas(
        code.k, code.k, _decode_consts_key(code.n, code.k, rows), shards,
        _interpret(interpret),
    )                                                   # [k, B, Sk]
    b, sk = out.shape[1], out.shape[2]
    return jnp.moveaxis(out, 0, 1).reshape(b, code.k * sk)


def decode_bitwise_xla(code: RSCode, shards: jax.Array, rows) -> jax.Array:
    """Bit-sliced decode in plain XLA (portable fast path)."""
    rows = tuple(int(r) for r in rows)
    out = _encode_bitwise(
        (_decode_consts_key(code.n, code.k, rows), code.k, code.k), shards
    )
    b, sk = out.shape[1], out.shape[2]
    return jnp.moveaxis(out, 0, 1).reshape(b, code.k * sk)


def decode_device(code: RSCode, shards: jax.Array, rows) -> jax.Array:
    """Platform-dispatched decode (mirrors ``encode_device``)."""
    if not pallas_interpret():
        return decode_pallas(code, shards, rows, interpret=False)
    return decode_bitwise_xla(code, shards, rows)
