"""Pallas TPU kernels for the paper's compute hot-spots.

Layout (one directory per kernel):
  bloom/     — blocked-Bloom build / probe / transfer (paper §3.2)
  semijoin/  — open-addressing hash build/probe (Yannakakis baseline §2.2)
  flashattn/ — serving-path attention (LM architectures; framework layer)

Each kernel ships three files:
  <name>.py  — pl.pallas_call body + BlockSpec tiling (TPU target)
  ops.py     — jit'd public wrapper
  ref.py     — pure-jnp oracle; tests sweep shapes/dtypes and
               assert_allclose kernel-vs-ref

`resolve_interpret` is the one place that decides whether a kernel runs
compiled or in the Pallas interpreter.
"""
from __future__ import annotations

from typing import Optional


def resolve_interpret(flag: Optional[bool] = None) -> bool:
    """Interpret mode for a Pallas call: the caller's explicit `flag`,
    else on for the `cpu` platform (the test posture) and off for `tpu`.
    Any other platform raises — these kernels target the TPU, and a
    silent interpreter run there would hide the device."""
    if flag is not None:
        return bool(flag)
    import jax
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels target TPU; no interpret-mode default for "
        f"platform {platform!r} (pass interpret= explicitly)")
