"""Long-context attention: ring attention + all-to-all sequence parallelism.

Net-new TPU-first scope.  The reference scales *rows of data*, never sequence
length (SURVEY.md section 2.2: no sequence/context parallelism anywhere in
the fork) -- but a TPU framework must treat long context as first-class, so
this module provides the two canonical strategies over a sequence-sharded
mesh axis:

- :func:`ring_attention` -- blockwise (flash-style) online-softmax attention
  where K/V blocks rotate around the ``sp`` ring via ``lax.ppermute``.  Each
  device holds ``T/P`` of the sequence; peak memory is O(T/P * T/P) per step
  instead of O(T^2), and the K/V transfer for step ``s+1`` overlaps the
  compute of step ``s`` (XLA schedules the ppermute DMA concurrently over
  ICI).  Exact (not approximate): the online max/denominator accumulation
  reproduces full softmax attention to float tolerance.
- :func:`ulysses_attention` -- the all-to-all alternative: switch from
  sequence-sharding to head-sharding (``all_to_all`` over ``sp``), run each
  head group's *full-sequence* attention locally, switch back.  Two
  all-to-alls per call; needs ``num_heads % P == 0``.

Both are ``shard_map``-ped over a ``Mesh`` axis and differentiable (JAX
differentiates through the loop and the collectives), and both reduce to
:func:`reference_attention` on a 1-device mesh.

Conventions: ``q, k, v`` are ``(batch, seq, heads, head_dim)``, sharded on
``seq`` over the mesh axis; causal masking uses global positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from asyncframework_tpu.parallel.mesh import resolve_shard_map

_NEG = -1e30  # mask fill / softmax-max init: finite so (-inf) - (-inf) never NaNs


def reference_attention(q, k, v, causal: bool = False):
    """Single-device full softmax attention (the correctness oracle)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_accumulate(q, k, v, m, l, o, mask):
    """One flash step: fold a K/V block into the running (max, denom, out).

    ``q``: (B, Tq, H, D); ``k``/``v``: (B, Tk, H, D); ``m``/``l``: (B, H, Tq)
    float32; ``o``: (B, Tq, H, D) float32; ``mask``: (Tq, Tk) or None.
    Accumulation is float32 regardless of input dtype (flash-attention
    practice: bf16 inputs, fp32 running state -- the per-step corr rescale
    compounds rounding otherwise).
    """
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG)
    # local stats for this block, then the ONE shared flash rescale
    # (_merge_stats) -- the same fold the Pallas path uses, so the two
    # block kernels can never drift numerically
    m_b = s.max(axis=-1)                         # (B, H, Tq) f32
    p = jnp.exp(s - m_b[..., None])              # (B, H, Tq, Tk) f32
    l_b = p.sum(axis=-1)
    o_b = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return _merge_stats(m, l, o, m_b, l_b, o_b)


def _merge_stats(m, l, o, m_b, l_b, o_b):
    """Fold a block's local softmax stats into the running (m, l, o) --
    the standard flash rescale, shared by the XLA and Pallas block paths."""
    m_new = jnp.maximum(m, m_b)
    c_old = jnp.exp(m - m_new)
    c_new = jnp.exp(m_b - m_new)
    l_new = l * c_old + l_b * c_new
    o_new = (
        o * c_old.transpose(0, 2, 1)[..., None]
        + o_b * c_new.transpose(0, 2, 1)[..., None]
    )
    return m_new, l_new, o_new


def ring_attention(
    q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = False,
    block_kernel: str = "xla", interpret: bool = False,
):
    """Exact attention over a sequence-sharded mesh axis via a K/V ring.

    Device ``p`` starts with its own K/V block and at ring step ``s`` holds
    the block originally on device ``(p - s) mod P`` (ppermute sends each
    block to the next device).  Causal masking uses global positions, so
    fully-masked future blocks contribute nothing (their probabilities
    underflow to zero against the running max).

    ``block_kernel``: "xla" runs the per-step block attention as fused XLA
    (:func:`_block_accumulate`); "pallas" offloads it to the hand-tiled
    :func:`~asyncframework_tpu.ops.pallas_kernels.chunk_attention` kernel
    and merges the returned (o, m, l) stats with the same flash rescale.
    ``interpret=True`` runs that kernel in the Pallas interpreter (the CPU
    tests pass it); it is never inferred from the backend.
    """
    if block_kernel not in ("xla", "pallas"):
        raise ValueError("block_kernel must be 'xla' or 'pallas'")
    n_dev = mesh.shape[axis]
    if q.shape[1] % n_dev:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by mesh axis size {n_dev}"
        )
    if q.shape[1] != k.shape[1]:
        # the block-position causal mask assumes aligned q/k positions;
        # cross-attention-style tq != tk would be silently wrong
        raise ValueError(
            f"ring_attention requires equal q/k seq lens, got {q.shape[1]} "
            f"vs {k.shape[1]}"
        )

    # check_vma must be off for the pallas block path: the pallas
    # interpreter's internal pad/slice mixes varying and invariant
    # operands, which strict vma checking rejects (a JAX interpreter
    # limitation, not a sharding bug -- the XLA path keeps the check)
    use_vma = block_kernel != "pallas"

    @functools.partial(
        resolve_shard_map(),
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        check_vma=use_vma,
    )
    def ring(ql, kl, vl):
        p_idx = jax.lax.axis_index(axis)
        P_sz = n_dev  # static mesh axis size
        b, tq, h, d = ql.shape
        t_local = kl.shape[1]
        # pcast to varying: the accumulators become device-varying on the sp
        # axis (the loop body's outputs are, via axis_index), so carry types
        # match.  Accumulators are f32 (see _block_accumulate).
        def varying(x):
            if not use_vma:
                return x  # vma tracking off: pcast is meaningless
            return jax.lax.pcast(x, (axis,), to="varying")

        m0 = varying(jnp.full((b, h, tq), _NEG, jnp.float32))
        l0 = varying(jnp.zeros((b, h, tq), jnp.float32))
        o0 = varying(jnp.zeros(ql.shape, jnp.float32))
        q_pos = p_idx * tq + jnp.arange(tq)

        def fold(kb, vb, m, l, o, mask):
            if block_kernel == "pallas":
                from asyncframework_tpu.ops.pallas_kernels import (
                    chunk_attention,
                )

                o_b, m_b, l_b = chunk_attention(
                    ql, kb, vb, mask, interpret=interpret,
                )
                return _merge_stats(m, l, o, m_b, l_b, o_b)
            return _block_accumulate(ql, kb, vb, m, l, o, mask)

        def accumulate(s, kb, vb, m, l, o):
            if causal:
                k_block = (p_idx - s) % P_sz
                k_pos = k_block * t_local + jnp.arange(t_local)
                mask = q_pos[:, None] >= k_pos[None, :]
                # a block strictly in the future (k_block > p_idx) is fully
                # masked: skip its einsums entirely -- halves causal FLOPs
                return jax.lax.cond(
                    k_block > p_idx,
                    lambda m, l, o: (m, l, o),
                    lambda m, l, o: fold(kb, vb, m, l, o, mask),
                    m, l, o,
                )
            return fold(kb, vb, m, l, o, None)

        def step(s, carry):
            kb, vb, m, l, o = carry
            m, l, o = accumulate(s, kb, vb, m, l, o)
            perm = [(j, (j + 1) % P_sz) for j in range(P_sz)]
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return kb, vb, m, l, o

        # P-1 rotate-and-accumulate steps, then the final block WITHOUT the
        # trailing ppermute (its output would be discarded -- one wasted
        # rotation of the K and V shards over ICI per call otherwise)
        kb, vb, m, l, o = jax.lax.fori_loop(
            0, P_sz - 1, step, (kl, vl, m0, l0, o0)
        )
        m, l, o = accumulate(P_sz - 1, kb, vb, m, l, o)
        out = o / l.transpose(0, 2, 1)[..., None]
        return out.astype(ql.dtype)

    return ring(q, k, v)


def ulysses_attention(
    q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = False,
    block_kernel: str = "xla", pallas_block: int = 512,
    interpret: bool = False,
):
    """All-to-all sequence parallelism (Ulysses-style): reshard seq->heads,
    attend over the full sequence per local head group, reshard back.

    ``block_kernel="pallas"`` folds the full-sequence attention through
    :func:`~asyncframework_tpu.ops.pallas_kernels.chunk_attention` in
    ``pallas_block``-sized K/V blocks merged by the shared flash rescale,
    instead of the XLA reference path; ``interpret`` as in
    :func:`ring_attention`.
    """
    if block_kernel not in ("xla", "pallas"):
        raise ValueError("block_kernel must be 'xla' or 'pallas'")
    n_dev = mesh.shape[axis]
    h = q.shape[2]
    if h % n_dev:
        raise ValueError(f"heads {h} not divisible by mesh axis size {n_dev}")
    for name, t in (("q", q.shape[1]), ("k", k.shape[1])):
        if t % n_dev:
            raise ValueError(
                f"{name} seq len {t} not divisible by mesh axis size {n_dev}"
            )
    if causal and q.shape[1] != k.shape[1]:
        # reference aligns the causal mask bottom-right for tq != tk; the
        # resharded local attention here would mask with absolute positions
        raise ValueError(
            f"causal ulysses_attention requires equal q/k seq lens, got "
            f"{q.shape[1]} vs {k.shape[1]}"
        )

    @functools.partial(
        resolve_shard_map(),
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        check_vma=block_kernel != "pallas",  # see ring_attention
    )
    def ulysses(ql, kl, vl):
        # (B, T/P, H, D) --all_to_all--> (B, T, H/P, D)
        def seq_to_heads(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        def heads_to_seq(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = seq_to_heads(ql), seq_to_heads(kl), seq_to_heads(vl)
        if block_kernel == "pallas":
            from asyncframework_tpu.ops.pallas_kernels import chunk_attention

            tq, tk = qh.shape[1], kh.shape[1]
            # fold K/V in VMEM-sized blocks through the shared flash
            # rescale, as a lax.scan so the PROGRAM stays O(1) in sequence
            # length (a Python loop would inline tk/blk pallas calls), and
            # per-block masks from index arithmetic so nothing O(Tq*Tk)
            # ever materializes
            blk = min(tk, max(int(pallas_block), 8))
            pad_k = (-tk) % blk
            kh_p = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            vh_p = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            nb = (tk + pad_k) // blk
            b, _, hl, dh = qh.shape
            q_pos = jnp.arange(tq)

            def fold_block(carry, i):
                m, l, o = carry
                kb = jax.lax.dynamic_slice_in_dim(kh_p, i * blk, blk, 1)
                vb = jax.lax.dynamic_slice_in_dim(vh_p, i * blk, blk, 1)
                k_pos = i * blk + jnp.arange(blk)
                valid = k_pos[None, :] < tk  # padded K columns masked off
                if causal:
                    mask_b = (q_pos[:, None] >= k_pos[None, :]) & valid
                else:
                    mask_b = jnp.broadcast_to(valid, (tq, blk))
                o_b, m_b, l_b = chunk_attention(
                    qh, kb, vb, mask_b, interpret=interpret
                )
                return _merge_stats(m, l, o, m_b, l_b, o_b), None

            init = (
                jnp.full((b, hl, tq), _NEG, jnp.float32),
                jnp.zeros((b, hl, tq), jnp.float32),
                jnp.zeros(qh.shape, jnp.float32),
            )
            (m, l, o), _ = jax.lax.scan(
                fold_block, init, jnp.arange(nb)
            )
            oh = (o / l.transpose(0, 2, 1)[..., None]).astype(qh.dtype)
        else:
            oh = reference_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(oh)

    return ulysses(q, k, v)
