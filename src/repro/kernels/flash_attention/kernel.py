"""Blocked (flash) causal attention Pallas kernel.

Standard online-softmax formulation: the grid iterates (batch·head,
q_block); each program streams K/V blocks through VMEM keeping running
max/denominator/accumulator, so HBM traffic is O(S·d) instead of the
O(S²) score matrix — the 32k-prefill enabler on the TPU target.

BlockSpec tiling: q tile (block_q, d), k/v tiles (block_k, d) with d the
head dim (64–128, MXU-aligned); accumulators live in fp32 VMEM scratch.
The causal mask is applied per (q_block, k_block) tile pair; k blocks
beyond the diagonal are skipped entirely.

The wrapper handles GQA by repeating KV heads; the pure-jnp oracle is
``ref.py``; models use the XLA q-chunked attention by default on CPU
(interpret-mode Pallas is orders of magnitude slower than XLA:CPU) and
this kernel on the TPU target (``ArchConfig.use_flash_kernel``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, seq_len: int,
                  scale: float, causal: bool):
    q = q_ref[...].astype(jnp.float32) * scale          # (block_q, d)
    block_q, d = q.shape
    q_idx = pl.program_id(1)
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    num_k = seq_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T                  # (block_q, block_k)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + p @ v.astype(jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # only blocks at or below the diagonal contribute
        last = (q_idx + 1) * block_q
        num_live = (last + block_k - 1) // block_k
    else:
        num_live = num_k
    m, l, acc = jax.lax.fori_loop(0, num_live, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "causal", "interpret")
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, H, S, D)
    v: jax.Array,
    *,
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = True,
    interpret: bool = True,
) -> jax.Array:
    b, h, s, d = q.shape
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    scale = 1.0 / (d ** 0.5)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, seq_len=s, scale=scale, causal=causal
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
