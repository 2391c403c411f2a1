"""Geometry registration for the fused placement kernel.

The only kernel in the tree with ``input_output_aliases``: the window
arrays (t1/t2/valid) are updated in place by the §IV.A.1 commit, so the
three input refs share buffers with the first three outputs.  The
declaration states those buffers explicitly; the checker verifies each
aliased pair tiles identically (same block shape, index maps agreeing on
every grid point) and that no *undeclared* pair shares a buffer — the
exact edit that would silently corrupt fleet scheduler state.

Shapes are the kernel's replica-last operands after padding: the wrapper
transposes the batch axis last and pads it up to a multiple of the tile
with ``do=0`` replicas.
"""

from __future__ import annotations

from repro.analysis.pallas_check import BlockDecl, KernelGeometry, register
from repro.kernels.placement.placement import block_size

_MODULE = "repro.kernels.placement.placement"


def _case(B, Dev, CFG, T, W, block_b):
    bb = block_size(B, block_b)
    Bp = B + (-B) % bb
    n = Bp // bb
    win = lambda name, buf=None: BlockDecl(
        name, (Dev, CFG, T, W, Bp), (Dev, CFG, T, W, bb),
        lambda i: (0, 0, 0, 0, i), buffer=buf,
    )
    devp = lambda name: BlockDecl(
        name, (Dev, 1, Bp), (Dev, 1, bb), lambda i: (0, 0, i)
    )
    cfgp = lambda name: BlockDecl(
        name, (CFG, 1, Bp), (CFG, 1, bb), lambda i: (0, 0, i)
    )
    rep = lambda name: BlockDecl(name, (1, Bp), (1, bb), lambda i: (0, i))
    return KernelGeometry(
        kernel="placement", module=_MODULE,
        case=f"B{B}Dev{Dev}CFG{CFG}T{T}W{W}bb{bb}",
        grid=(n,),
        inputs=(
            devp("q1"), devp("dl"), rep("src"), rep("do"), cfgp("min_dur"),
            win("t1", "win_t1"), win("t2", "win_t2"),
            win("valid", "win_valid"),
        ),
        outputs=(
            win("t1_out", "win_t1"), win("t2_out", "win_t2"),
            win("valid_out", "win_valid"), rep("ok"), rep("sel"),
            rep("start"), rep("dur"), rep("use4"), rep("drop"),
        ),
        # matches fused_place's input_output_aliases={5: 0, 6: 1, 7: 2}
        aliases={5: 0, 6: 1, 7: 2},
    )


@register("placement")
def geometries():
    # paper testbed geometry (Dev=4, CFG=3, T=2, W=16) at the fleet
    # engine's tile (block_b=128)
    return [
        _case(8, 4, 3, 2, 16, 128),      # one tile of the whole batch
        _case(1, 4, 3, 2, 16, 128),      # B=1 calib path
        _case(300, 4, 3, 2, 16, 128),    # padded: 300 -> 384, three tiles
        _case(4096, 4, 3, 2, 16, 128),   # sweep batch of 4096 on one chip
        # sharded fleet: each mesh shard launches over its local batch
        # (global B / shards).
        _case(2, 4, 3, 2, 16, 128),      # B=16 @ 8 shards; calib quick grid
        _case(32, 4, 3, 2, 16, 128),     # B=256 @ 8 shards
        _case(256, 4, 3, 2, 16, 128),    # B=2048 @ 8 shards; B=256 batches
        _case(1024, 4, 3, 2, 16, 128),   # B=4096 @ 4 shards
    ]
