"""Ring attention — context/sequence parallelism over the mesh ``seq`` axis.

The reference has no long-context machinery (SURVEY §5: N/A in the reference;
the Transformer4Rec-style sequential template introduces it as a new
capability). Design follows the blockwise ring-attention recipe: the sequence
is sharded over the ``seq`` mesh axis, each device keeps its Q chunk pinned
while K/V chunks rotate around the ring via ``ppermute`` (ICI
neighbor-to-neighbor traffic, no all-gather), and softmax is accumulated
online flash-style (running max / numerator / denominator, fp32 accumulators,
bf16 QKᵀ and PV matmuls on the MXU).

Causality across chunks is by chunk index: a device at ring position ``i``
fully attends chunks ``j < i``, causally masks its own chunk, and skips
``j > i`` (their scores are -inf; the online update is a no-op).

Public entry: :func:`ring_attention` (to be called inside ``shard_map`` with
the ``seq`` axis in scope) and :func:`ring_attention_sharded` (wraps the
shard_map for [B, L, H, D] inputs sharded B→data, L→seq).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def _chunk_attend(q, k, v, mask, m, l, o):
    """One online-softmax update with an extra additive mask.

    q: [B, Lq, H, D]; k/v: [B, Lk, H, D]; mask: [Lq, Lk] additive (0/-inf);
    m/l: [B, H, Lq] running max / denominator; o: [B, Lq, H, D] numerator.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    # [B, H, Lq, Lk] scores on the MXU in bf16, accumulated fp32
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) * scale
    s = s + mask[None, None, :, :]
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) → use where
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_new, -jnp.inf))
    p = jnp.exp(s - m_new[..., None])  # [B, H, Lq, Lk]
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _mark_varying(x, axes):
    """Mark ``x`` device-varying over manual ``axes`` (scan carries that a
    ppermute makes varying must start out typed that way)."""
    return jax.lax.pcast(x, axes, to="varying")


def ring_attention(q, k, v, axis_name: str, pvary_axes=None):
    """Causal ring attention for one sequence shard (call under shard_map).

    q, k, v: [B, Lc, H, D] — this device's chunk of the globally
    length-L = Lc × axis_size sequence. Returns [B, Lc, H, D] in q's dtype.
    ``pvary_axes``: all manual axes in scope (defaults to just ``axis_name``);
    fresh accumulators must be marked varying over every one of them.
    """
    s_size = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, lc, h, d = q.shape
    neg = jnp.float32(-jnp.inf)
    causal = jnp.where(
        jnp.arange(lc)[:, None] >= jnp.arange(lc)[None, :], 0.0, neg
    )  # within-chunk causal mask
    zeros = jnp.zeros((lc, lc), jnp.float32)

    def body(carry, step):
        kc, vc, m, l, o = carry
        j = (my - step) % s_size  # origin chunk index of the K/V we now hold
        mask = jnp.where(j == my, causal, jnp.where(j < my, zeros, neg + zeros))
        m, l, o = _chunk_attend(q, kc, vc, mask, m, l, o)
        kc = jax.lax.ppermute(kc, axis_name, [(i, (i + 1) % s_size) for i in range(s_size)])
        vc = jax.lax.ppermute(vc, axis_name, [(i, (i + 1) % s_size) for i in range(s_size)])
        return (kc, vc, m, l, o), None

    # fresh accumulators must be marked varying over the manual axes, or scan
    # rejects the carry (unvarying input vs varying output)
    axes = tuple(pvary_axes) if pvary_axes is not None else (axis_name,)
    _vary = functools.partial(_mark_varying, axes=axes)
    m0 = _vary(jnp.full((b, h, lc), neg))
    l0 = _vary(jnp.zeros((b, h, lc), jnp.float32))
    o0 = _vary(jnp.zeros((b, lc, h, d), jnp.float32))
    (kc, vc, m, l, o), _ = jax.lax.scan(
        body, (k, v, m0, l0, o0), jnp.arange(s_size)
    )
    del kc, vc
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, data_axis: str = "data",
                           seq_axis: str = "seq"):
    """shard_map wrapper: q/k/v [B, L, H, D] with B sharded over ``data_axis``
    and L over ``seq_axis``."""
    spec = P(data_axis, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis,
                          pvary_axes=mesh.axis_names),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def flash_block_size(l: int):
    """Block size for the flash kernel at sequence length ``l``, or ``None``
    when the materializing reference is the right path (short or
    tile-unaligned sequences). The kernel requires the block to divide L;
    the largest of 512/256/128 wins (512 measured fastest on v5e)."""
    if l < 256 or l % 128 != 0:
        return None
    return 512 if l % 512 == 0 else (256 if l % 256 == 0 else 128)


def causal_attention(q, k, v):
    """Single-device causal attention for the training hot path.

    On TPU with long sequences: the Pallas flash-attention kernel (online
    softmax over VMEM blocks — the [L, L] score matrix never touches HBM,
    which at d_model 512 / seq 512 removes ~2 GB of HBM traffic per layer
    per step). Block sizes are pinned to min(L, 512) everywhere: measured on
    v5e, the kernel's defaults lose to the materializing reference (137 vs
    98 ms/step on the scaled sequential config) while 512-blocks win (85
    ms/step). Short sequences (< 256 or non-multiple-of-128) take the jnp
    reference — tile-aligned blocking needs room to pay off, and the
    reference doubles as the kernel's correctness oracle in tests.
    Layout: [B, L, H, DH] in and out (the kernel wants [B, H, L, DH])."""
    from incubator_predictionio_tpu.parallel.mesh import kernel_backend

    l = q.shape[1]
    # the stock flash kernel has no interpreter switch, so only a real TPU
    # takes the kernels here
    is_tpu = kernel_backend() == "mosaic"
    if is_tpu:
        from incubator_predictionio_tpu.ops.attention import (
            causal_mha_small_head,
            fits_small_head_kernel,
        )

        bq, lq, h, dh = q.shape
        if fits_small_head_kernel(bq, lq, h, dh):
            # small-head/VMEM-resident shapes: the stock flash kernel's
            # per-(batch, head) grid pays more pipeline overhead than
            # arithmetic (ops/attention.py; measured 44 → ~12 ms of an
            # 84 ms step on the benched sequential config)
            out = causal_mha_small_head(
                q.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
                k.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
                v.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
            )
            return out.transpose(0, 2, 1, 3).astype(q.dtype)
    b = flash_block_size(l)
    if is_tpu and b is not None:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes,
            flash_attention,
        )
        # block_b=2: at small head dims each (batch, head) program does
        # little MXU work; pairing batch rows per program measured 5.9 →
        # 4.5 ms/layer fwd+bwd on the v5e sequential config (b_b=4 regresses)
        bb = 2 if q.shape[0] % 2 == 0 else 1
        bs = BlockSizes(
            block_q=b, block_k_major=b, block_k=b, block_b=bb,
            block_q_major_dkv=b, block_k_major_dkv=b,
            block_k_dkv=b, block_q_dkv=b,
            block_k_major_dq=b, block_k_dq=b, block_q_dq=b,
        )
        out = flash_attention(
            q.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
            k.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
            v.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
            causal=True,
            sm_scale=1.0 / math.sqrt(q.shape[-1]),
            block_sizes=bs,
        )
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    return causal_attention_reference(q, k, v)


def causal_attention_reference(q, k, v):
    """Single-device causal attention (also the correctness oracle for the
    ring tests): QK/PV matmuls run in bfloat16 on the MXU with fp32
    accumulation; softmax stays fp32."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) * scale
    lq = q.shape[1]
    mask = jnp.where(jnp.arange(lq)[:, None] >= jnp.arange(lq)[None, :], 0.0,
                     -jnp.inf)
    s = s + mask[None, None, :, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
