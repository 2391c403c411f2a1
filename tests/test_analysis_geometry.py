"""Pallas geometry checker tests: the registry covers every production
kernel and reports it clean; each seeded fixture trips its violation
class; and the racy fixture kernel *actually corrupts data* when run, so
the static write-race check is proven against executable ground truth.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis.fixtures.racy_kernel import (
    GEOMETRY_PROVIDERS,
    racy_sum,
    racy_sum_oracle,
)
from repro.analysis.pallas_check import (
    BlockDecl,
    KernelGeometry,
    MAX_GRID_POINTS,
    check_all,
    check_geometry,
    load_registry,
)

PRODUCTION_KERNELS = {
    "flash_attention", "flash_decode", "placement",
    "ssd_scan", "ssm_scan",
}


def test_registry_covers_all_production_kernels():
    assert PRODUCTION_KERNELS <= set(load_registry())


def test_production_geometry_is_clean():
    report = check_all()
    assert report["ok"], report["violations"]
    assert report["n_kernels"] >= len(PRODUCTION_KERNELS)
    # every kernel actually enumerated a non-trivial grid
    for name, entry in report["kernels"].items():
        assert entry["grid_points_checked"] > 0, name
        assert entry["cases"], name


@pytest.mark.parametrize("fixture,kind", [
    ("race", "write-race"),
    ("oob", "oob"),
    ("alias", "alias"),
])
def test_fixture_trips_expected_violation(fixture, kind):
    violations = []
    for g in GEOMETRY_PROVIDERS[fixture]():
        violations.extend(check_geometry(g))
    assert violations, f"fixture {fixture} produced no violation"
    assert {v.kind for v in violations} == {kind}


def test_fixture_report_fails_via_check_all():
    report = check_all({"fixture_race": GEOMETRY_PROVIDERS["race"]})
    assert not report["ok"]
    assert report["n_violations"] == 1
    assert report["kernels"]["fixture_race"]["violations"]


def test_racy_kernel_really_corrupts():
    """Executable ground truth: the same BlockSpec the checker flags
    statically silently drops the first block's contribution when run
    (interpret mode = sequential grid, last writer wins)."""
    x = jnp.arange(8, dtype=jnp.float32)
    got = np.asarray(racy_sum(x))
    want = np.asarray(racy_sum_oracle(x))
    assert not np.allclose(got, want), "race did not manifest"
    # last grid point (i=1, scale 2.0) won every lane
    np.testing.assert_allclose(got, np.asarray(x[4:]) * 2.0)


def test_reduction_axis_admits_shared_output_block():
    """A sequential accumulation axis (flash-attention style) must NOT be
    reported as a race when declared — and must be when not."""
    def geom(red):
        return KernelGeometry(
            kernel="k", module="m", case="c", grid=(2, 3),
            inputs=(),
            outputs=(BlockDecl("o", (2, 8), (1, 8),
                               lambda i, k: (i, 0)),),
            reduction_axes=frozenset({1} if red else ()),
        )
    assert check_geometry(geom(red=True)) == []
    bad = check_geometry(geom(red=False))
    assert bad and bad[0].kind == "write-race"


def test_masked_dim_admits_ragged_edge():
    def geom(masked):
        decl = BlockDecl("o", (10,), (4,), lambda i: (i,),
                         masked_dims=frozenset({0} if masked else ()))
        return KernelGeometry(kernel="k", module="m", case="c",
                              grid=(3,), inputs=(), outputs=(decl,))
    assert check_geometry(geom(masked=True)) == []
    bad = check_geometry(geom(masked=False))
    assert bad and bad[0].kind == "oob"


def test_declared_alias_must_tile_identically():
    win = lambda im: BlockDecl("w", (8,), (4,), im, buffer="b")
    g = KernelGeometry(
        kernel="k", module="m", case="c", grid=(2,),
        inputs=(win(lambda i: (i,)),),
        outputs=(win(lambda i: (1 - i,)),),       # disagreeing map
        aliases={0: 0},
    )
    bad = check_geometry(g)
    assert bad and bad[0].kind == "alias"


def test_spec_rank_mismatch_reported():
    g = KernelGeometry(
        kernel="k", module="m", case="c", grid=(1,),
        inputs=(BlockDecl("x", (4, 4), (4,), lambda i: (i,)),),
        outputs=(),
    )
    bad = check_geometry(g)
    assert bad and bad[0].kind == "spec"


def test_grid_enumeration_is_capped():
    g = KernelGeometry(
        kernel="k", module="m", case="c",
        grid=(MAX_GRID_POINTS + 1,),
        inputs=(),
        outputs=(BlockDecl("o", (4,), (4,), lambda i: (0,)),),
    )
    with pytest.raises(ValueError, match="MAX_GRID_POINTS"):
        check_geometry(g)
