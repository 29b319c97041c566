"""Pallas TPU kernel: fused flash-attention forward (causal / windowed GQA).

This is the fix for the dominant *memory* roofline term of the train/prefill
cells (EXPERIMENTS.md §Perf): the XLA attention materializes fp32
(B,H,Sq,Sk) scores through HBM (~51 GB per layer-microbatch on qwen3-moe),
while this kernel keeps the score block, softmax state and accumulator in
VMEM — HBM traffic is exactly Q + K + V + O.

Grid: (B*Hq, nq, nk), k-blocks innermost.  Online softmax state (m, l) and
the fp32 accumulator live in VMEM scratch and persist across the k-block
axis; the output block is written once on the last k step.  GQA is handled
in the BlockSpec index maps (query head h reads kv head h // group), so K/V
are never expanded.

MXU alignment: block sizes are multiples of 128; head_dim is padded to 128
lanes by the wrapper when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_BLK = 128
K_BLK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window, q_blk: int, k_blk: int, nk: int,
            scale: float, sk_real: int):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (q_blk, dh)
    k = k_ref[0].astype(jnp.float32)                  # (k_blk, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (q_blk, k_blk)

    q_pos = qb * q_blk + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q_blk, k_blk), 0)
    k_pos = kb * k_blk + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q_blk, k_blk), 1)
    mask = k_pos < sk_real          # never attend to padded keys
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    v = v_ref[0].astype(jnp.float32)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (q_blk, dh)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kb == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_blk", "k_blk", "interpret"))
def flash_attention(
    q: jax.Array,                 # (B, Sq, Hq, Dh)
    k: jax.Array,                 # (B, Sk, Hkv, Dh)
    v: jax.Array,
    causal: bool = True,
    window=None,
    q_blk: int = Q_BLK,
    k_blk: int = K_BLK,
    interpret: bool = True,
) -> jax.Array:
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = 1.0 / float(dh) ** 0.5

    # lane-align head_dim and pad sequence lengths to block multiples
    dh_pad = max(128, ((dh + 127) // 128) * 128)
    nq = -(-sq // q_blk)
    nk = -(-sk // k_blk)
    qp = jnp.pad(q, ((0, 0), (0, nq * q_blk - sq), (0, 0),
                     (0, dh_pad - dh)))
    kp = jnp.pad(k, ((0, 0), (0, nk * k_blk - sk), (0, 0),
                     (0, dh_pad - dh)))
    vp = jnp.pad(v, ((0, 0), (0, nk * k_blk - sk), (0, 0),
                     (0, dh_pad - dh)))
    # (B*H, S, dh) layout so the grid's first axis picks (batch, head)
    qp = qp.transpose(0, 2, 1, 3).reshape(b * hq, nq * q_blk, dh_pad)
    kp = kp.transpose(0, 2, 1, 3).reshape(b * hkv, nk * k_blk, dh_pad)
    vp = vp.transpose(0, 2, 1, 3).reshape(b * hkv, nk * k_blk, dh_pad)

    def kv_index(bh, qb, kb):
        batch = bh // hq
        head = bh % hq
        return (batch * hkv + head // group, kb, 0)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, q_blk=q_blk, k_blk=k_blk,
        nk=nk, scale=scale, sk_real=sk)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_blk, dh_pad), lambda bh, qb, kb: (bh, qb, 0)),
            pl.BlockSpec((1, k_blk, dh_pad), kv_index),
            pl.BlockSpec((1, k_blk, dh_pad), kv_index),
        ],
        out_specs=pl.BlockSpec((1, q_blk, dh_pad),
                               lambda bh, qb, kb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, nq * q_blk, dh_pad),
                                       q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk, dh_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    out = out.reshape(b, hq, nq * q_blk, dh_pad).transpose(0, 2, 1, 3)
    return out[:, :sq, :, :dh]
