"""Erasure-coding tests: GF(2^8) arithmetic, RS round-trips, any-k-of-n
recovery, and NumPy-vs-XLA agreement (SURVEY.md §4 "kernel unit tests")."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ec import gf
from raft_tpu.ec.rs import RSCode


class TestGF:
    def test_mul_matches_schoolbook(self):
        # carryless polynomial multiply mod 0x11d, checked exhaustively on a
        # random sample
        rng = np.random.default_rng(0)
        a = rng.integers(0, 256, 500, dtype=np.uint8)
        b = rng.integers(0, 256, 500, dtype=np.uint8)

        def slow_mul(x, y):
            acc = 0
            x, y = int(x), int(y)
            while y:
                if y & 1:
                    acc ^= x
                x <<= 1
                if x & 0x100:
                    x ^= gf.POLY
                y >>= 1
            return acc

        want = np.array([slow_mul(x, y) for x, y in zip(a, b)], np.uint8)
        np.testing.assert_array_equal(gf.mul(a, b), want)

    def test_field_axioms_on_sample(self):
        rng = np.random.default_rng(1)
        a = rng.integers(1, 256, 200, dtype=np.uint8)
        b = rng.integers(1, 256, 200, dtype=np.uint8)
        c = rng.integers(0, 256, 200, dtype=np.uint8)
        np.testing.assert_array_equal(gf.mul(a, b), gf.mul(b, a))
        np.testing.assert_array_equal(gf.mul(a, gf.inv(a)), np.ones_like(a))
        # distributivity: a*(b^c) == a*b ^ a*c
        np.testing.assert_array_equal(
            gf.mul(a, b ^ c), gf.mul(a, b) ^ gf.mul(a, c)
        )

    def test_mat_inv_roundtrip(self):
        rng = np.random.default_rng(2)
        for n in (2, 3, 5):
            # random invertible matrix: retry until nonsingular
            while True:
                A = rng.integers(0, 256, (n, n), dtype=np.uint8)
                try:
                    Ainv = gf.mat_inv(A)
                    break
                except IndexError:
                    continue
            np.testing.assert_array_equal(
                gf.mat_mul(A, Ainv), np.eye(n, dtype=np.uint8)
            )

    def test_mul_table(self):
        t = gf.mul_table(7)
        np.testing.assert_array_equal(
            t, gf.mul(np.full(256, 7, np.uint8), np.arange(256, dtype=np.uint8))
        )


@pytest.mark.parametrize("n,k", [(3, 2), (5, 3), (7, 4), (9, 6)])
class TestRSCode:
    def test_systematic_roundtrip(self, n, k):
        rng = np.random.default_rng(n * 16 + k)
        S = 12 * k
        data = rng.integers(0, 256, (10, S), dtype=np.uint8)
        shards = code_of(n, k).encode(data)
        assert shards.shape == (n, 10, S // k)
        # systematic: the first k shard rows ARE the byte-sliced data
        np.testing.assert_array_equal(
            code_of(n, k).unsplit(shards[:k]), data
        )

    def test_any_k_of_n_recovers(self, n, k):
        rng = np.random.default_rng(n * 31 + k)
        code = code_of(n, k)
        S = 8 * k
        data = rng.integers(0, 256, (4, S), dtype=np.uint8)
        shards = code.encode(data)
        for rows in itertools.combinations(range(n), k):
            got = code.decode(shards[list(rows)], rows)
            np.testing.assert_array_equal(got, data, err_msg=f"rows={rows}")

    def test_xla_encode_matches_numpy(self, n, k):
        rng = np.random.default_rng(n * 7 + k)
        code = code_of(n, k)
        S = 16 * k
        data = rng.integers(0, 256, (6, S), dtype=np.uint8)
        want = code.encode(data)
        got = np.asarray(code.encode_jax(jnp.asarray(data)))
        np.testing.assert_array_equal(got, want)

    def test_xla_decode_matches_numpy(self, n, k):
        rng = np.random.default_rng(n * 13 + k)
        code = code_of(n, k)
        S = 8 * k
        data = rng.integers(0, 256, (5, S), dtype=np.uint8)
        shards = code.encode(data)
        rows = list(range(n - k, n))  # worst case: all parity-heavy suffix
        got = np.asarray(code.decode_jax(jnp.asarray(shards[rows]), rows))
        np.testing.assert_array_equal(got, data)


def code_of(n, k):
    return RSCode(n=n, k=k)


class TestErasureScenarios:
    def test_two_erasures_rs53(self):
        """BASELINE config 3 shape: RS(5,3), f=2 loss, full recovery."""
        rng = np.random.default_rng(9)
        code = RSCode(5, 3)
        data = rng.integers(0, 256, (1024, 255), dtype=np.uint8)  # 255=3*85
        shards = code.encode(data)
        surviving = [0, 3, 4]  # lost shards 1, 2 (one data, one... 1 is data)
        got = code.decode(shards[surviving], surviving)
        np.testing.assert_array_equal(got, data)

    def test_generator_is_mds(self):
        """Every k x k submatrix of G invertible (spot-check by decoding)."""
        code = RSCode(6, 3)
        for rows in itertools.combinations(range(6), 3):
            D = code.decode_matrix(rows)  # raises if singular
            assert D.shape == (3, 3)


class TestKernels:
    """Pallas parity kernel (interpret mode on CPU) and the bitwise-XLA
    path, both against the NumPy oracle."""

    @pytest.mark.parametrize("n,k", [(3, 2), (5, 3)])
    def test_bitwise_xla_matches_numpy(self, n, k):
        from raft_tpu.ec.kernels import encode_bitwise_xla

        rng = np.random.default_rng(n + k)
        code = RSCode(n, k)
        S = 32 * k
        data = rng.integers(0, 256, (16, S), dtype=np.uint8)
        got = np.asarray(encode_bitwise_xla(code, jnp.asarray(data)))
        np.testing.assert_array_equal(got, code.encode(data))

    @pytest.mark.parametrize("n,k", [(3, 2), (5, 3)])
    def test_pallas_matches_numpy(self, n, k):
        from raft_tpu.ec.kernels import encode_pallas

        rng = np.random.default_rng(n * k)
        code = RSCode(n, k)
        S = 32 * k
        data = rng.integers(0, 256, (16, S), dtype=np.uint8)
        got = np.asarray(encode_pallas(code, jnp.asarray(data)))
        np.testing.assert_array_equal(got, code.encode(data))

    def test_pallas_recovers_after_erasure(self):
        from raft_tpu.ec.kernels import encode_pallas

        rng = np.random.default_rng(42)
        code = RSCode(5, 3)
        data = rng.integers(0, 256, (8, 96), dtype=np.uint8)
        shards = np.asarray(encode_pallas(code, jnp.asarray(data)))
        rows = [1, 3, 4]
        np.testing.assert_array_equal(code.decode(shards[rows], rows), data)

    @pytest.mark.parametrize("n,k", [(3, 2), (5, 3)])
    def test_bitwise_decode_matches_numpy(self, n, k):
        from itertools import combinations

        from raft_tpu.ec.kernels import decode_bitwise_xla

        rng = np.random.default_rng(7 * n + k)
        code = RSCode(n, k)
        S = 32 * k
        data = rng.integers(0, 256, (16, S), dtype=np.uint8)
        shards = code.encode(data)
        for rows in combinations(range(n), k):   # every serving subset
            got = np.asarray(
                decode_bitwise_xla(code, jnp.asarray(shards[list(rows)]), rows)
            )
            np.testing.assert_array_equal(got, data)

    @pytest.mark.parametrize("n,k", [(3, 2), (5, 3)])
    def test_pallas_decode_matches_numpy(self, n, k):
        from raft_tpu.ec.kernels import decode_pallas, encode_pallas

        rng = np.random.default_rng(9 * n + k)
        code = RSCode(n, k)
        S = 32 * k
        data = rng.integers(0, 256, (16, S), dtype=np.uint8)
        shards = np.asarray(encode_pallas(code, jnp.asarray(data)))
        rows = [1] + list(range(n - k + 1, n))   # parity-heavy subset
        got = np.asarray(
            decode_pallas(code, jnp.asarray(shards[rows]), rows)
        )
        np.testing.assert_array_equal(got, data)

    @pytest.mark.parametrize("n,k", [(3, 2), (5, 3)])
    def test_fused_encode_fold_matches_unfused(self, n, k):
        """The fused encode+fold kernel (production EC ingest path on TPU)
        must be byte-identical to fold_shards_device(encode_device(...));
        exercised here through the Pallas interpret path."""
        from raft_tpu.ec.kernels import (
            _encode_fold_pallas,
            _parity_consts_key,
            encode_device,
            fold_shards_device,
        )

        rng = np.random.default_rng(11 * n + k)
        code = RSCode(n, k)
        data = rng.integers(0, 256, (16, 32 * k), dtype=np.uint8)
        want = np.asarray(fold_shards_device(encode_device(code, jnp.asarray(data))))
        got = np.asarray(_encode_fold_pallas(
            code.k, code.m, _parity_consts_key(n, k), jnp.asarray(data),
            True,
        ))
        np.testing.assert_array_equal(got, want)

    def test_device_fold_matches_host_fold(self):
        """fold_shards_device's bitcast packing must equal the host
        np.view(int32) little-endian fold byte for byte — the two feed the
        same device log layout (engine EC tick vs heal/re-serve paths)."""
        from raft_tpu.core.state import fold_rows
        from raft_tpu.ec.kernels import fold_shards_device

        rng = np.random.default_rng(7)
        shards = rng.integers(0, 256, (5, 8, 12), dtype=np.uint8)
        np.testing.assert_array_equal(
            np.asarray(fold_shards_device(jnp.asarray(shards))),
            np.asarray(fold_rows(shards)),
        )
