"""Dispatching wrapper for the fused fleet placement — the single source
of the backend policy; the fleet engine routes every placement attempt
through here."""

from __future__ import annotations

import warnings

import jax

from repro.kernels.placement.placement import LANES, fused_place
from repro.kernels.placement.ref import fused_place_ref


def resolve_backend(backend: str) -> tuple[bool, bool]:
    """``(use_kernel, interpret)`` for a placement backend name.

    "auto" → the Pallas kernel on a TPU, the jnp oracle elsewhere (the
    CPU default of the test suite); "kernel" → the kernel everywhere,
    interpreted off-TPU (with a warning: its speed there says nothing
    about the chip); "ref" → the jnp oracle.
    """
    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto":
        return on_tpu, False
    if backend == "kernel":
        if not on_tpu:
            warnings.warn(
                f"placement backend 'kernel' on {jax.default_backend()!r}: "
                f"running the Pallas kernel in interpret mode",
                RuntimeWarning, stacklevel=3,
            )
        return True, not on_tpu
    if backend == "ref":
        return False, False
    raise ValueError(f"unknown placement backend: {backend!r}")


def fused_place_op(t1, t2, valid, min_dur, q1, dl, src, do, *,
                   backend: str = "auto", cfg_pref: int = 1,
                   cfg_fallback: int = 2, block_b: int = LANES):
    """One fused placement attempt for the whole fleet batch (backend
    policy in ``resolve_backend``).  Returns the oracle's output tuple
    either way.

    ``block_b`` is the kernel's replica tile: a multiple of 128, or at
    least the batch.  Under the sharded fleet engine each mesh shard
    launches its own kernel over the B/shards local batch, so the tile is
    a per-shard knob (FleetParams.placement_block_b) — any new (local-B,
    block_b) launch geometry must be registered in the kernel's
    geometry.py for the analysis gate.
    """
    use_kernel, interpret = resolve_backend(backend)
    if use_kernel:
        return fused_place(
            t1, t2, valid, min_dur, q1, dl, src, do,
            cfg_pref=cfg_pref, cfg_fallback=cfg_fallback,
            interpret=interpret, block_b=block_b,
        )
    return fused_place_ref(
        t1, t2, valid, min_dur, q1, dl, src, do,
        cfg_pref=cfg_pref, cfg_fallback=cfg_fallback,
    )
