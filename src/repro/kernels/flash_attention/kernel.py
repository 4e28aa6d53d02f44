"""Pallas TPU flash attention (forward) with causal mask, GQA, sliding
window, and logit soft-capping.

Design (TPU-adapted, not a CUDA port):
  * grid = (batch * q_heads, S / block_q); each step owns one query tile.
  * K/V arrive as full (S, D) planes for the step's KV head (BlockSpec maps
    the GQA head group); the kernel walks KV tiles with an in-register
    online-softmax carry (m, l, acc) — the classic flash recurrence.
  * causal + window masking is done per KV tile with iota comparisons; KV
    tiles wholly outside the (causal ∩ window) band are skipped via the
    loop bounds, so sliding-window attention costs O(S · window) not O(S²).
  * logits are computed in fp32 on the MXU (preferred_element_type) and
    soft-capped with tanh when requested (gemma2).

VMEM per step: q tile (block_q × D) + K/V tiles (2 × block_k × D) + acc
(block_q × D) fp32 ≈ (block_q + 2·block_k + block_q) · D · 4 B — with
block_q = block_k = 512, D = 128: ~1 MiB. MXU dims are 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                 seq_len: int, causal: bool, window: int | None,
                 softcap: float | None, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (block_q, D)
    D = q.shape[-1]

    q_pos0 = qi * block_q
    # KV range actually needed by this query tile
    hi_pos = q_pos0 + block_q if causal else seq_len
    n_hi = pl.cdiv(hi_pos, block_k) if causal else seq_len // block_k
    if window is not None:
        lo_pos = jnp.maximum(q_pos0 - (window - 1), 0)
        n_lo = lo_pos // block_k
    else:
        n_lo = 0

    def body(kv_i, carry):
        m_prev, l_prev, acc = carry
        kv = pl.ds(kv_i * block_k, block_k)
        k = k_ref[0, kv, :].astype(jnp.float32)
        v = v_ref[0, kv, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kv_i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q,), dtype=jnp.float32)
    acc0 = jnp.zeros((block_q, D), dtype=jnp.float32)
    m, l, acc = jax.lax.fori_loop(n_lo, n_hi, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           softcap: float | None = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0.

    Heads are flattened into the grid's first axis; the BlockSpec index map
    routes each q head to its GQA KV head (h // group_size).
    """
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0

    qf = q.reshape(B * Hq, S, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)
    grid = (B * Hq, S // block_q)

    def q_map(h, i):
        return (h, i, 0)

    def kv_map(h, i):
        b = h // Hq
        hh = (h % Hq) // group
        return (b * Hkv + hh, 0, 0)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, causal=causal, window=window,
                          softcap=softcap, scale=1.0 / (D ** 0.5)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, S, D), kv_map),
            pl.BlockSpec((1, S, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, S, D)
