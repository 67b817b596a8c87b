"""Pallas TPU kernel: blocked online-softmax (flash) attention with GQA.

Serving hot-spot for the content-cache framework: prefill at 32k context and
single-token decode against a long KV cache. Standard three-dim grid
(batch*heads, q blocks, kv blocks) with the kv dimension 'arbitrary'
(sequential) so the f32 accumulator, running max and running sum live in VMEM
scratch across kv iterations.

VMEM budget per program at the default blocks (bq = bk = 128, D = 128):
q/k/v blocks 3 * 128*128*2B = 96 KB + acc/m/l scratch ~70 KB — comfortably
inside VMEM, MXU-aligned (128 multiples).

GQA is handled in the k/v index maps: query head h reads kv head h // group,
so no K/V replication is materialised.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, bq, D)
    k_ref,  # (1, bk, D)
    v_ref,  # (1, bk, D)
    o_ref,  # (1, bq, D)
    acc_ref,  # (bq, D) f32 scratch
    m_ref,  # (bq, 1) f32 scratch
    l_ref,  # (bq, 1) f32 scratch
    *,
    scale: float,
    causal: bool,
    kv_len: int,
    bq: int,
    bk: int,
    nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal block skip: block is live iff its first kv id <= last q id
    if causal:
        live = ki * bk <= qi * bq + bq - 1
    else:
        live = ki * bk < kv_len  # skip fully-padded tail blocks

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        col = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = col < kv_len
        if causal:
            row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask &= col <= row
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        # rows that saw no live kv (fully padded) produce 0, not NaN
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KH, Skv, D)
    v: jax.Array,  # (B, KH, Skv, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_len: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """Returns (B, H, Sq, D) attention output; f32 accumulation inside."""
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    if h % kh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kh}")
    group = h // kh
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    kv_len = skv if kv_len is None else kv_len

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    sq_pad = (sq + bq - 1) // bq * bq
    skv_pad = (skv + bk - 1) // bk * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0))) if sq_pad != sq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0))) if skv_pad != skv else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0))) if skv_pad != skv else v

    qf = qp.reshape(b * h, sq_pad, d)
    kf = kp.reshape(b * kh, skv_pad, d)
    vf = vp.reshape(b * kh, skv_pad, d)
    nq = sq_pad // bq
    nk = skv_pad // bk

    def kv_index(bh, qi, ki):
        return ((bh // h) * kh + (bh % h) // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        kv_len=kv_len,
        bq=bq,
        bk=bk,
        nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq_pad, d)[:, :, :sq, :]
