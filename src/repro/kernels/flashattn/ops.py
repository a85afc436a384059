"""Public wrapper: GQA expansion, head folding, block padding."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flashattn import flashattn as _k


def flash_attention(q, k, v, q_pos, kv_pos, kv_valid, *,
                    causal: bool = True, window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q [B,Sq,H,D]; k/v [B,Skv,KVH,D] (KVH | H); positions [B,S*].
    Returns [B,Sq,H,D]."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    skv = k.shape[1]

    pad_q = (-sq) % _k.Q_BLK
    pad_k = (-skv) % _k.KV_BLK
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_k)))
        kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad_k)))

    sqp, skvp = q.shape[1], k.shape[1]
    # fold heads into batch: [B*H, S, D]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sqp, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, skvp, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, skvp, d)
    qpf = jnp.repeat(q_pos, h, axis=0)
    kpf = jnp.repeat(kv_pos, h, axis=0)
    kvf = jnp.repeat(kv_valid, h, axis=0)

    out = _k.flash_pallas(qf, kf, vf, qpf, kpf, kvf, causal=causal,
                          window=window,
                          interpret=resolve_interpret(interpret))
    out = out.reshape(b, h, sqp, d).transpose(0, 2, 1, 3)
    return out[:, :sq]
