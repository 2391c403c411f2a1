"""Fused placement kernel (pl.pallas_call + BlockSpec).

One launch per placement attempt for the whole fleet batch: the §IV.B.2
multi-containment query, slot/device selection, most-overlapping-track
(victim window) selection and the §IV.A.1 multi-remainder fan-out commit.

Layout.  The kernel works replica-last: the wrapper transposes the
engine's ``[B, Dev, CFG, T, W]`` window arrays to ``[Dev, CFG, T, W, B]``
at the kernel boundary (``ref.to_lanes``) and back afterwards
(``ref.from_lanes``), so the replicas fill the TPU's 128-lane axis and W
the sublanes:

    grid = (replica blocks,)
    block: windows [Dev, CFG, T, W, block_b]; q1/dl [Dev, 1, block_b];
           min_dur [CFG, 1, block_b]; src/do and outputs [1, block_b]

``block_b`` is a multiple of 128, or the whole batch when that is
smaller (the TPU's rule for the minor block dim).  Per-replica and
per-device values broadcast along leading dims and every reduction keeps
its axis, so no shape cast moves data into the minor tile.  The
boundary transposes are full passes over the window arrays in XLA on
every attempt; keeping ``FleetState`` replica-last would remove them.

Under a profiler the boundary work (``to_lanes``, ``from_lanes``, the
dtype casts and lane padding) runs under the named scope
``placement/layout`` and the launch under ``placement/kernel``.

The window arrays are aliased input→output (``input_output_aliases``), so
the commit is an in-place VMEM update; replicas whose ``do`` mask is off
are passed through bit-identical.  The body traces
``ref._fused_place_math``, the same trace as the oracle, so the
equivalence tests assert exact equality.

VMEM: 6 window blocks (3 in, 3 out) of Dev·CFG·T·W·block_b · 4 B,
double-buffered, plus the body's temporaries.  For a described TPU v5e
at (Dev=4, CFG=3, T=2, W=16) Mosaic needs a scoped limit of 2.9 MiB at
block_b=128, 5.9 MiB at 256 and 12 MiB at 512, so the default 16 MiB
holds and no ``vmem_limit_bytes`` is set.  tests/test_tpu_compile.py
compiles it for v5e at B = 4096, 1024, 2 and 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.placement.ref import (
    _fused_place_math, from_lanes, to_lanes,
)

#: the TPU lane width: a block's minor dim is a multiple of it or the
#: whole array dim.
LANES = 128


def block_size(B: int, block_b: int) -> int:
    """The replica tile for a batch of ``B``: the whole batch when it fits
    in one tile, else ``block_b``, which must then be lane-aligned."""
    if B <= block_b:
        return B
    if block_b % LANES:
        raise ValueError(
            f"placement block_b={block_b} must be a multiple of {LANES} "
            f"when it tiles a batch of {B} (the TPU's minor block dim)"
        )
    return block_b


def _placement_kernel(q1_ref, dl_ref, src_ref, do_ref, md_ref, t1_ref,
                      t2_ref, valid_ref, t1_out, t2_out, valid_out, ok_out,
                      sel_out, start_out, dur_out, use4_out, drop_out, *,
                      cfg_pref: int, cfg_fallback: int):
    nt1, nt2, nv, ok, sel, start, dur, use4, n_drop = _fused_place_math(
        t1_ref[...], t2_ref[...], valid_ref[...] != 0, md_ref[...],
        q1_ref[...], dl_ref[...], src_ref[...], do_ref[...] != 0,
        cfg_pref=cfg_pref, cfg_fallback=cfg_fallback,
    )
    t1_out[...] = nt1
    t2_out[...] = nt2
    valid_out[...] = nv.astype(jnp.int32)
    ok_out[...] = ok.astype(jnp.int32)
    sel_out[...] = sel
    start_out[...] = start
    dur_out[...] = dur
    use4_out[...] = use4.astype(jnp.int32)
    drop_out[...] = n_drop


@functools.partial(
    jax.jit,
    static_argnames=("cfg_pref", "cfg_fallback", "block_b", "interpret"),
)
def fused_place(t1, t2, valid, min_dur, q1, dl, src, do, *,
                cfg_pref: int = 1, cfg_fallback: int = 2,
                block_b: int = LANES, interpret: bool = False):
    """Fused placement attempt for a whole fleet batch in one launch.

    t1, t2: [B, Dev, CFG, T, W] f32; valid: same shape (bool/int);
    min_dur: [B, CFG] f32; q1, dl: [B, Dev] f32; src: [B] i32;
    do: [B] bool/int.  Returns
    ``(t1', t2', valid' bool, ok bool, sel i32, start f32, dur f32,
    use4 bool, n_dropped i32)`` — the same tuple as the jnp oracle.
    """
    B, Dev, CFG, T, W = t1.shape
    bb = block_size(B, block_b)
    pad = (-B) % bb
    with jax.named_scope("placement/layout"):
        t1, t2, valid, md, q1, dl, src, do = to_lanes(
            t1, t2, valid, min_dur, q1, dl, src, do
        )
        valid = valid.astype(jnp.int32)
        do = do.astype(jnp.int32)
        if pad:
            # padded replicas have do=0, so they never commit
            lane_pad = lambda a: jnp.pad(
                a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))
            t1, t2, valid, md, q1, dl, src, do = map(
                lane_pad, (t1, t2, valid, md, q1, dl, src, do)
            )
    Bp = B + pad

    win_spec = pl.BlockSpec(
        (Dev, CFG, T, W, bb), lambda i: (0, 0, 0, 0, i)
    )
    devp_spec = pl.BlockSpec((Dev, 1, bb), lambda i: (0, 0, i))
    cfgp_spec = pl.BlockSpec((CFG, 1, bb), lambda i: (0, 0, i))
    rep_spec = pl.BlockSpec((1, bb), lambda i: (0, i))
    win = lambda dt: jax.ShapeDtypeStruct((Dev, CFG, T, W, Bp), dt)
    rep = lambda dt: jax.ShapeDtypeStruct((1, Bp), dt)
    kernel = functools.partial(
        _placement_kernel, cfg_pref=cfg_pref, cfg_fallback=cfg_fallback
    )
    with jax.named_scope("placement/kernel"):
        out = pl.pallas_call(
            kernel,
            grid=(Bp // bb,),
            in_specs=[devp_spec, devp_spec, rep_spec, rep_spec, cfgp_spec,
                      win_spec, win_spec, win_spec],
            out_specs=[win_spec, win_spec, win_spec, rep_spec, rep_spec,
                       rep_spec, rep_spec, rep_spec, rep_spec],
            out_shape=[
                win(jnp.float32), win(jnp.float32), win(jnp.int32),
                rep(jnp.int32), rep(jnp.int32), rep(jnp.float32),
                rep(jnp.float32), rep(jnp.int32), rep(jnp.int32),
            ],
            # the commit is an in-place update of the window arrays
            input_output_aliases={5: 0, 6: 1, 7: 2},
            interpret=interpret,
        )(q1, dl, src, do, md, t1, t2, valid)
    with jax.named_scope("placement/layout"):
        nt1, nt2, nv, ok, sel, start, dur, use4, n_drop = (
            a[..., :B] for a in out
        )
        return from_lanes(nt1, nt2, nv != 0, ok != 0, sel, start, dur,
                          use4 != 0, n_drop)
