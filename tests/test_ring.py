"""Long-context attention tests on the 8-device virtual CPU mesh.

Ring attention and Ulysses all-to-all sequence parallelism are net-new
TPU-first scope (the reference has no sequence dimension at all -- SURVEY.md
section 2.2); correctness is exactness against single-device full softmax
attention, including gradients through the collectives.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from asyncframework_tpu.parallel import (
    make_mesh,
    reference_attention,
    ring_attention,
    ulysses_attention,
)


def make_qkv(rng, b=2, t=64, h=8, d=16):
    q = rng.normal(size=(b, t, h, d)).astype(np.float32)
    k = rng.normal(size=(b, t, h, d)).astype(np.float32)
    v = rng.normal(size=(b, t, h, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.fixture(scope="module")
def sp_mesh():
    import jax as _jax

    return make_mesh(8, axis_names=("sp",), devices=_jax.devices()[:8])


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, rng, sp_mesh, causal):
        q, k, v = make_qkv(rng)
        want = reference_attention(q, k, v, causal=causal)
        got = ring_attention(q, k, v, sp_mesh, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_single_device_mesh_degenerates(self, rng):
        mesh = make_mesh(1, axis_names=("sp",), devices=jax.devices()[:1])
        q, k, v = make_qkv(rng, t=32)
        got = ring_attention(q, k, v, mesh)
        want = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_uneven_seq_rejected(self, rng, sp_mesh):
        q, k, v = make_qkv(rng, t=30)  # 30 % 8 != 0
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, k, v, sp_mesh)

    def test_mismatched_qk_seq_rejected(self, rng, sp_mesh):
        """tq != tk would make the block-position causal mask silently wrong
        (reference aligns bottom-right); must be a hard error."""
        q, _, _ = make_qkv(rng, t=32)
        _, k, v = make_qkv(rng, t=64)
        with pytest.raises(ValueError, match="equal q/k seq lens"):
            ring_attention(q, k, v, sp_mesh, causal=True)

    def test_bf16_inputs_accumulate_in_f32(self, rng, sp_mesh):
        """bf16 inputs: ring's error vs an fp32 oracle must stay in the same
        band as single-shot bf16 attention (fp32 running state), not grow
        with ring steps."""
        q, k, v = make_qkv(rng, t=64)
        oracle = np.asarray(reference_attention(q, k, v))
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ring_err = np.abs(
            np.asarray(ring_attention(qb, kb, vb, sp_mesh), np.float32)
            - oracle
        ).max()
        ref_err = np.abs(
            np.asarray(reference_attention(qb, kb, vb), np.float32) - oracle
        ).max()
        assert ring_err < 2.5 * ref_err + 1e-3
        # and the output dtype follows the inputs
        assert ring_attention(qb, kb, vb, sp_mesh).dtype == jnp.bfloat16

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.slow
    def test_gradients_match_reference(self, rng, sp_mesh, causal):
        """Differentiability through ppermute + fori_loop (training path)."""
        q, k, v = make_qkv(rng, b=1, t=32, h=4, d=8)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, sp_mesh, causal=causal) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
            )

    def test_causal_first_positions_attend_self_only(self, rng, sp_mesh):
        """Row 0 of causal attention must equal v[0] exactly (only itself)."""
        q, k, v = make_qkv(rng, b=1, t=64, h=8, d=16)
        out = ring_attention(q, k, v, sp_mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(out[0, 0]), np.asarray(v[0, 0]), rtol=1e-5, atol=1e-6
        )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, rng, sp_mesh, causal):
        q, k, v = make_qkv(rng)  # h=8 divisible by 8 devices
        want = reference_attention(q, k, v, causal=causal)
        got = ulysses_attention(q, k, v, sp_mesh, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_head_divisibility_enforced(self, rng, sp_mesh):
        q, k, v = make_qkv(rng, h=6)
        with pytest.raises(ValueError, match="heads"):
            ulysses_attention(q, k, v, sp_mesh)

    def test_agrees_with_ring(self, rng, sp_mesh):
        q, k, v = make_qkv(rng)
        a = ring_attention(q, k, v, sp_mesh, causal=True)
        b = ulysses_attention(q, k, v, sp_mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


class TestPallasBlockKernel:
    """ring_attention with the hand-tiled chunk_attention Pallas kernel
    (interpret mode on CPU) must agree with the oracle exactly like the
    XLA block path."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_block_matches_reference(self, rng, sp_mesh, causal):
        q, k, v = (
            rng.normal(size=(2, 32, 2, 16)).astype(np.float32)
            for _ in range(3)
        )
        got = ring_attention(
            q, k, v, sp_mesh, causal=causal, block_kernel="pallas",
            interpret=True,
        )
        want = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_unknown_kernel_rejected(self, rng, sp_mesh):
        q = rng.normal(size=(1, 8, 1, 4)).astype(np.float32)
        with pytest.raises(ValueError):
            ring_attention(q, q, q, sp_mesh, block_kernel="nope")


class TestChunkAttentionKernel:
    # the second shape is past the kernel's 512 block edge on both axes:
    # several query blocks, several key blocks folded through the running
    # (m, l, acc) scratch, ragged padding on each
    @pytest.mark.parametrize("B,T,Tk,H,D", [(2, 24, 18, 3, 20),
                                            (1, 520, 1030, 2, 8)])
    def test_stats_match_oracle(self, rng, B, T, Tk, H, D):
        import math

        import jax.numpy as jnp

        from asyncframework_tpu.ops.pallas_kernels import chunk_attention

        q = rng.normal(size=(B, T, H, D)).astype(np.float32)
        k = rng.normal(size=(B, Tk, H, D)).astype(np.float32)
        v = rng.normal(size=(B, Tk, H, D)).astype(np.float32)
        mask = rng.random((T, Tk)) > 0.3
        o, m, l = chunk_attention(q, k, v, mask, interpret=True)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
        mw = s.max(-1)
        p = jnp.exp(s - mw[..., None])
        np.testing.assert_allclose(np.asarray(m), np.asarray(mw), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(l), np.asarray(p.sum(-1)), rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(o),
            np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p, v)),
            rtol=1e-4, atol=1e-5,
        )


class TestUlyssesPallas:
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_matches_reference(self, rng, sp_mesh, causal):
        from asyncframework_tpu.parallel import ulysses_attention

        q, k, v = (
            rng.normal(size=(2, 32, 8, 16)).astype(np.float32)
            for _ in range(3)
        )
        got = ulysses_attention(
            q, k, v, sp_mesh, causal=causal, block_kernel="pallas",
            interpret=True,
        )
        want = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_blockwise_fold(self, rng, sp_mesh, causal):
        """pallas_block smaller than the sequence exercises the K/V fold
        loop (the VMEM-bounded path real long sequences take)."""
        from asyncframework_tpu.parallel import ulysses_attention

        q, k, v = (
            rng.normal(size=(1, 32, 8, 8)).astype(np.float32)
            for _ in range(3)
        )
        got = ulysses_attention(
            q, k, v, sp_mesh, causal=causal, block_kernel="pallas",
            pallas_block=8, interpret=True,
        )
        want = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
        )
