"""Causal GQA flash attention as a Pallas TPU kernel.

Adaptation notes (GPU FlashAttention -> TPU, per DESIGN.md §3):
* the online-softmax tiling maps to VMEM blocks instead of SM shared
  memory: each grid step owns a (BLOCK_Q, head_dim) query tile resident in
  VMEM and streams (BLOCK_K, head_dim) K/V tiles;
* tile sizes are MXU-aligned (multiples of 128 on the contracting and lane
  dims; head_dim is typically 128);
* the grid iterates (batch, kv_head, q_group, q_block); the innermost KV
  loop is a fori_loop *inside* the kernel so the running (m, l, acc) stay in
  registers/VMEM -- the TPU analogue of FA2's register accumulation;
* causal masking skips fully-masked KV tiles via the loop upper bound
  (block-level early exit -- no wasted MXU work past the diagonal).

q: (B, S, H, hd) -> kernel works on one (kv-head, group) slice at a time;
GQA means K/V tiles are shared across the G query heads of the group, which
is why the group dim lives INSIDE the q tile (better KV reuse in VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                 seq_len: int, scale: float, causal: bool):
    qi = pl.program_id(3)
    q = q_ref[...].astype(jnp.float32) * scale      # (block_q, hd)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def kv_step(j, carry):
        m, l, acc = carry
        k = k_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                  # (block_q, block_k)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    # causal block-level early exit: only blocks up to the diagonal
    if causal:
        upper = jnp.minimum((qi + 1) * block_q + block_k - 1,
                            seq_len) // block_k
    else:
        upper = seq_len // block_k
    m, l, acc = jax.lax.fori_loop(0, upper, kv_step, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, block_q: int = 256,
                           block_k: int = 256,
                           interpret: bool = False) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    # regroup: (B, KV, G, S, hd) so one grid cell = one (b, kv, g, q-block)
    qr = jnp.moveaxis(q.reshape(B, S, KV, G, hd), 1, 3)
    kr = jnp.moveaxis(k, 1, 2)                       # (B, KV, S, hd)
    vr = jnp.moveaxis(v, 1, 2)

    grid = (B, KV, G, S // block_q)
    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_q=block_q, block_k=block_k,
                          seq_len=S, scale=1.0 / (hd ** 0.5), causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, None, block_q, hd),
                         lambda b, kv, g, qi: (b, kv, g, qi, 0)),
            pl.BlockSpec((None, None, S, hd),
                         lambda b, kv, g, qi: (b, kv, 0, 0)),
            pl.BlockSpec((None, None, S, hd),
                         lambda b, kv, g, qi: (b, kv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, None, block_q, hd),
                               lambda b, kv, g, qi: (b, kv, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S // block_q * block_q, hd),
                                       q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return jnp.moveaxis(out, 3, 1).reshape(B, S, H, hd)
