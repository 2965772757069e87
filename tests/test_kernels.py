"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.bsmm_pairs import _in_calls as bsmm_pairs_in_calls

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape)
    return jnp.asarray(x, dtype)


class TestBatchedGemm:
    @pytest.mark.parametrize("bs", [8, 16, 32, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, bs, dtype):
        p = 16
        a, b = _rand((p, bs, bs), dtype), _rand((p, bs, bs), dtype)
        out = ops.batched_gemm(a, b, use_pallas=True, interpret=True)
        want = ref.batched_gemm_ref(a, b)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2 * bs
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), atol=tol)

    @pytest.mark.parametrize("p", [2, 6, 24])
    def test_odd_batch_sizes(self, p):
        a, b = _rand((p, 16, 16), jnp.float32), _rand((p, 16, 16),
                                                      jnp.float32)
        out = ops.batched_gemm(a, b, use_pallas=True, interpret=True)
        np.testing.assert_allclose(out, ref.batched_gemm_ref(a, b),
                                   atol=1e-5)

    def test_xla_fallback_identical_contract(self):
        a, b = _rand((8, 16, 16), jnp.float32), _rand((8, 16, 16),
                                                      jnp.float32)
        np.testing.assert_allclose(
            ops.batched_gemm(a, b, use_pallas=False),
            ops.batched_gemm(a, b, use_pallas=True, interpret=True),
            atol=1e-5)


class TestBsmmPairs:
    def _case(self, cap_a, cap_b, cap_c, n_pairs, bs, dtype=jnp.float32,
              seed=0):
        rng = np.random.default_rng(seed)
        ab = jnp.asarray(rng.standard_normal((cap_a, bs, bs)), dtype)
        bb = jnp.asarray(rng.standard_normal((cap_b, bs, bs)), dtype)
        sa = jnp.asarray(rng.integers(0, cap_a, n_pairs), jnp.int32)
        sb = jnp.asarray(rng.integers(0, cap_b, n_pairs), jnp.int32)
        seg = jnp.sort(jnp.asarray(rng.integers(0, cap_c, n_pairs),
                                   jnp.int32))
        return ab, bb, sa, sb, seg

    @pytest.mark.parametrize("bs", [8, 16, 32])
    def test_sweep_block_sizes(self, bs):
        ab, bb, sa, sb, seg = self._case(12, 12, 6, 30, bs)
        out = ops.bsmm_pairs(ab, bb, sa, sb, seg, cap_c=6,
                             use_pallas=True, interpret=True)
        want = ref.bsmm_pairs_ref(ab, bb, sa, sb, seg, 6)
        np.testing.assert_allclose(out, want, atol=1e-4)

    def test_invalid_pairs_dropped(self):
        ab, bb, sa, sb, seg = self._case(8, 8, 4, 16, 8)
        seg = seg.at[-5:].set(4)  # invalid marker == cap_c
        out = ops.bsmm_pairs(ab, bb, sa, sb, seg, cap_c=4,
                             use_pallas=True, interpret=True)
        want = ref.bsmm_pairs_ref(ab, bb, sa, sb, seg, 4)
        np.testing.assert_allclose(out, want, atol=1e-4)

    def test_unvisited_slots_zero(self):
        """C slots with no contributing pair must come back zero."""
        bs = 8
        ab = _rand((4, bs, bs), jnp.float32)
        bb = _rand((4, bs, bs), jnp.float32)
        # all pairs hit slot 0; slots 1..3 unvisited
        sa = jnp.zeros((4,), jnp.int32)
        sb = jnp.zeros((4,), jnp.int32)
        seg = jnp.zeros((4,), jnp.int32)
        out = ops.bsmm_pairs(ab, bb, sa, sb, seg, cap_c=4,
                             use_pallas=True, interpret=True)
        assert np.all(np.asarray(out[1:]) == 0)

    @pytest.mark.parametrize("max_pairs", [4, 7, 30])
    def test_split_into_calls_is_bitwise_one_call(self, max_pairs):
        """Pair streams longer than one call's SMEM bound run as several
        calls over one C buffer; segments cut between calls, invalid
        pairs at the tail and unvisited slots give bitwise the one-call
        result."""
        ab, bb, sa, sb, seg = self._case(8, 8, 6, 30, 8, seed=3)
        seg = seg.at[-4:].set(6)            # invalid tail (== cap_c)
        seg = jnp.where(seg == 5, 4, seg)   # last slot unvisited
        out = bsmm_pairs_in_calls(ab, bb, sa, sb, seg, cap_c=6,
                                  interpret=True, max_pairs=max_pairs)
        one = bsmm_pairs_in_calls(ab, bb, sa, sb, seg, cap_c=6,
                                  interpret=True, max_pairs=64)
        np.testing.assert_array_equal(out, one)
        np.testing.assert_allclose(
            out, ref.bsmm_pairs_ref(ab, bb, sa, sb, seg, 6), atol=1e-4)
        assert np.all(np.asarray(out[5]) == 0)

    def test_bfloat16(self):
        ab, bb, sa, sb, seg = self._case(8, 8, 4, 16, 16, dtype=jnp.bfloat16)
        out = ops.bsmm_pairs(ab, bb, sa, sb, seg, cap_c=4,
                             use_pallas=True, interpret=True)
        want = ref.bsmm_pairs_ref(ab, bb, sa, sb, seg, 4)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), atol=1.0)


class TestBandedAttention:
    @pytest.mark.parametrize("window,block", [(16, 16), (32, 16), (32, 32)])
    def test_sweep_windows(self, window, block):
        h, s, d = 2, 64, 16
        q, k, v = (_rand((h, s, d), jnp.float32) for _ in range(3))
        out = ops.banded_attention(q, k, v, window=window, block_q=block,
                                   block_kv=block, use_pallas=True,
                                   interpret=True)
        want = ref.banded_attention_ref(q, k, v, window)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_noncausal(self):
        h, s, d = 1, 64, 8
        q, k, v = (_rand((h, s, d), jnp.float32) for _ in range(3))
        out = ops.banded_attention(q, k, v, window=16, block_q=16,
                                   block_kv=16, causal=False,
                                   use_pallas=True, interpret=True)
        want = ref.banded_attention_ref(q, k, v, 16, causal=False)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_window_covers_all_equals_full_attention(self):
        """window >= S reduces to ordinary causal attention."""
        h, s, d = 1, 32, 8
        q, k, v = (_rand((h, s, d), jnp.float32) for _ in range(3))
        out = ops.banded_attention(q, k, v, window=32, block_q=16,
                                   block_kv=16, use_pallas=True,
                                   interpret=True)
        scores = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d)
        mask = np.tril(np.ones((s, s), bool))
        scores = jnp.where(mask[None], scores, -jnp.inf)
        want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_bfloat16(self):
        h, s, d = 2, 64, 16
        q, k, v = (_rand((h, s, d), jnp.bfloat16) for _ in range(3))
        out = ops.banded_attention(q, k, v, window=16, block_q=16,
                                   block_kv=16, use_pallas=True,
                                   interpret=True)
        want = ref.banded_attention_ref(q, k, v, 16)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), atol=3e-2)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_property_attention_rows_sum_via_uniform_v(seed):
    """With v = all-ones, banded attention returns exactly ones
    (softmax weights sum to 1 over the band)."""
    h, s, d = 1, 32, 8
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((h, s, d)), jnp.float32)
    v = jnp.ones((h, s, d), jnp.float32)
    out = ops.banded_attention(q, k, v, window=16, block_q=16, block_kv=16,
                               use_pallas=True, interpret=True)
    np.testing.assert_allclose(out, np.ones((h, s, d)), atol=1e-5)
