"""Flash-decode as a Pallas TPU kernel: split-K over the KV cache.

GPU flash-decoding splits the KV sequence across thread blocks and merges
partial softmax states; the TPU adaptation splits across *grid cells* --
each (batch, kv_head, split) cell reduces its S/n_splits slice of the cache
with an online softmax over VMEM tiles, emitting a partial
(out, max, sumexp) triple; a cheap renormalized merge in XLA combines the
splits.  This keeps every MXU op on (G x block_k x hd) tiles and the HBM
traffic at exactly one cache read -- decode is memory-bound, so the kernel's
job is to stream the cache at full bandwidth, not to save FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                   block_k: int, split_len: int, scale: float):
    si = pl.program_id(2)
    length = len_ref[0]
    q = q_ref[...].astype(jnp.float32) * scale        # (G, hd)
    G, hd = q.shape
    m = jnp.full((G,), NEG_INF, jnp.float32)
    l = jnp.zeros((G,), jnp.float32)
    acc = jnp.zeros((G, hd), jnp.float32)
    base = si * split_len

    def kv_step(j, carry):
        m, l, acc = carry
        k = k_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                   # (G, block_k)
        pos = base + j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_k), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    # only stream blocks that can contain valid positions
    nblocks = split_len // block_k
    valid_blocks = jnp.clip(
        (length - base + block_k - 1) // block_k, 0, nblocks)
    m, l, acc = jax.lax.fori_loop(0, valid_blocks, kv_step, (m, l, acc))
    o_ref[...] = acc.astype(o_ref.dtype)
    m_ref[...] = m
    l_ref[...] = l


@functools.partial(jax.jit, static_argnames=("n_splits", "block_k",
                                             "interpret"))
def decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            lengths: jax.Array, n_splits: int = 8,
                            block_k: int = 256,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k/v: (B, S, KV, hd); lengths: (B,). -> (B, H, hd)."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    while S % (n_splits * block_k) and n_splits > 1:
        n_splits //= 2
    block_k = min(block_k, S)
    assert S % (n_splits * block_k) == 0, (S, n_splits, block_k)
    split_len = S // n_splits

    qr = q.reshape(B, KV, G, hd)
    kr = jnp.moveaxis(k, 1, 2)        # (B, KV, S, hd)
    vr = jnp.moveaxis(v, 1, 2)
    grid = (B, KV, n_splits)
    o, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k,
                          split_len=split_len, scale=1.0 / (hd ** 0.5)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, kv, s: (b,)),
            pl.BlockSpec((None, None, G, hd), lambda b, kv, s: (b, kv, 0, 0)),
            pl.BlockSpec((None, None, split_len, hd),
                         lambda b, kv, s: (b, kv, s, 0)),
            pl.BlockSpec((None, None, split_len, hd),
                         lambda b, kv, s: (b, kv, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, G, hd),
                         lambda b, kv, s: (b, kv, s, 0, 0)),
            pl.BlockSpec((None, None, None, G),
                         lambda b, kv, s: (b, kv, s, 0)),
            pl.BlockSpec((None, None, None, G),
                         lambda b, kv, s: (b, kv, s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, n_splits, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, n_splits, G), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, n_splits, G), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, qr, kr, vr)
    # renormalized merge across splits (flash-decoding reduction)
    m_max = m.max(axis=2, keepdims=True)                  # (B,KV,1,G)
    alpha = jnp.exp(m - m_max)                            # (B,KV,ns,G)
    l_tot = (l * alpha).sum(axis=2)                       # (B,KV,G)
    o_tot = (o * alpha[..., None]).sum(axis=2)            # (B,KV,G,hd)
    out = o_tot / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(B, H, hd).astype(q.dtype)
