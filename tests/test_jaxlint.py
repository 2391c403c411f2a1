"""jaxlint tests: each rule fires on a minimal positive, stays silent on
the static-inference negatives (shape-derived values, static_argnames),
honors inline suppressions and the allowlist — and the repo itself lints
clean, which is the CI gate this PR adds.

Also pins the *fixes* the linter drove: window/availability arithmetic
stays 32-bit even under JAX_ENABLE_X64 (int64 iotas do not lower on TPU,
and several of these trace inside the Pallas placement kernel body).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.jaxlint import (
    iter_source_files,
    lint_paths,
    lint_source,
)
from repro.analysis.pallas_check import registered_modules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_ROOT = os.path.join(REPO_ROOT, "src", "repro")


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# rule positives
# ---------------------------------------------------------------------------

def test_tracer_leak_on_jitted_if():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    assert _rules(lint_source(src, "core/foo.py")) == ["tracer-leak"]


def test_tracer_leak_on_bool_coercion():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return bool(x > 0)\n"
    )
    assert _rules(lint_source(src, "core/foo.py")) == ["tracer-leak"]


def test_promotion_hazard_on_dtypeless_arange():
    src = (
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    return jnp.arange(n)\n"
    )
    assert _rules(lint_source(src, "core/foo.py")) == ["promotion-hazard"]
    # explicit dtype is the fix
    fixed = src.replace("jnp.arange(n)", "jnp.arange(n, dtype=jnp.int32)")
    assert lint_source(fixed, "core/foo.py") == []


def test_promotion_hazard_scoped_to_window_arithmetic_paths():
    src = (
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    return jnp.arange(n)\n"
    )
    # plotting/report code outside core|fleet|kernels|calib is exempt
    assert lint_source(src, "figures/foo.py") == []


def test_scan_donate_on_jitted_scan_without_donation():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(c, xs):\n"
        "    return jax.lax.scan(lambda c, x: (c + x, None), c, xs)[0]\n"
    )
    assert _rules(lint_source(src, "core/foo.py")) == ["scan-donate"]


def test_scan_donate_satisfied_by_donate_argnums():
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, donate_argnums=(0,))\n"
        "def f(c, xs):\n"
        "    return jax.lax.scan(lambda c, x: (c + x, None), c, xs)[0]\n"
    )
    assert lint_source(src, "core/foo.py") == []


def test_unregistered_pallas_call():
    src = (
        "from jax.experimental import pallas as pl\n"
        "def f(x):\n"
        "    return pl.pallas_call(lambda i, o: None, grid=(1,))(x)\n"
    )
    out = lint_source(src, "kernels/foo/foo.py", registered_paths=set())
    assert _rules(out) == ["unregistered-pallas-call"]
    assert lint_source(src, "kernels/foo/foo.py",
                       registered_paths={"kernels/foo/foo.py"}) == []


def test_host_transfer_on_device_get():
    src = (
        "import jax\n"
        "def f(stats):\n"
        "    return jax.device_get(stats)\n"
    )
    assert _rules(lint_source(src, "fleet/foo.py")) == ["host-transfer"]
    # rule is scoped to the fleet hot path
    assert lint_source(src, "figures/foo.py") == []


def test_host_transfer_on_numpy_in_scan_loop():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def f(c, xs):\n"
        "    c, _ = jax.lax.scan(lambda c, x: (c + x, None), c, xs)\n"
        "    return np.asarray(c)\n"
    )
    assert _rules(lint_source(src, "fleet/foo.py")) == ["host-transfer"]
    # numpy outside a scan-bearing function is host-side reduction code
    no_scan = (
        "import numpy as np\n"
        "def g(c):\n"
        "    return np.asarray(c)\n"
    )
    assert lint_source(no_scan, "fleet/foo.py") == []


def test_host_transfer_on_item_in_scan_loop():
    src = (
        "import jax\n"
        "def f(c, xs):\n"
        "    c, _ = jax.lax.scan(lambda c, x: (c + x, None), c, xs)\n"
        "    return c.sum().item()\n"
    )
    assert _rules(lint_source(src, "fleet/foo.py")) == ["host-transfer"]


def test_host_transfer_on_undonated_jit_expression():
    src = (
        "import jax\n"
        "def make(fn):\n"
        "    return jax.jit(fn)\n"
    )
    assert _rules(lint_source(src, "fleet/foo.py")) == ["host-transfer"]
    donated = src.replace("jax.jit(fn)", "jax.jit(fn, donate_argnums=(0,))")
    assert lint_source(donated, "fleet/foo.py") == []
    suppressed = src.replace(
        "    return jax.jit(fn)",
        "    # repro: lint-ok(host-transfer)\n    return jax.jit(fn)",
    )
    assert lint_source(suppressed, "fleet/foo.py") == []


def test_leaky_fixture_trips():
    fixture = os.path.join(SRC_ROOT, "analysis", "fixtures", "leaky_jit.py")
    findings = lint_paths(SRC_ROOT, [fixture])
    assert "tracer-leak" in _rules(findings)


# ---------------------------------------------------------------------------
# static-inference negatives (the zero-false-positive contract)
# ---------------------------------------------------------------------------

def test_shape_derived_branching_is_static():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    n = x.shape[0]\n"
        "    if n > 2:\n"
        "        return x\n"
        "    return x * 2\n"
    )
    assert lint_source(src, "core/foo.py") == []


def test_static_argnames_branching_is_static():
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('flag',))\n"
        "def f(x, *, flag=False):\n"
        "    if flag:\n"
        "        return x\n"
        "    return -x\n"
    )
    assert lint_source(src, "core/foo.py") == []


def test_nested_scan_body_params_are_traced():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(c, xs):\n"
        "    def body(c, x):\n"
        "        if x > 0:\n"
        "            return c, None\n"
        "        return c + 1, None\n"
        "    return jax.lax.scan(body, c, xs, unroll=1)[0]\n"
    )
    assert "tracer-leak" in _rules(lint_source(src, "core/foo.py"))


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

_LEAK = (
    "import jax\n"
    "@jax.jit\n"
    "def f(x):\n"
    "    if x > 0:{comment}\n"
    "        return x\n"
    "    return -x\n"
)


def test_inline_suppression_on_finding_line():
    src = _LEAK.format(comment="  # repro: lint-ok(tracer-leak)")
    assert lint_source(src, "core/foo.py") == []


def test_inline_suppression_wildcard_and_wrong_rule():
    assert lint_source(
        _LEAK.format(comment="  # repro: lint-ok(*)"), "core/foo.py"
    ) == []
    out = lint_source(
        _LEAK.format(comment="  # repro: lint-ok(scan-donate)"),
        "core/foo.py",
    )
    assert _rules(out) == ["tracer-leak"]


def test_allowlist_suppression():
    src = _LEAK.format(comment="")
    assert _rules(lint_source(src, "core/foo.py")) == ["tracer-leak"]
    assert lint_source(
        src, "core/foo.py", allowlist={("core/foo.py", "tracer-leak")}
    ) == []


# ---------------------------------------------------------------------------
# the gate itself
# ---------------------------------------------------------------------------

def test_repo_is_lint_clean():
    """The CI gate: src/repro lints clean with the committed allowlist
    and the kernel geometry registry as the pallas_call ground truth."""
    registered = {
        m.replace("repro.", "").replace(".", "/") + ".py"
        for m in registered_modules()
    }
    findings = lint_paths(SRC_ROOT, registered_paths=registered)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_fixture_dirs_excluded_from_default_scan():
    files = list(iter_source_files(SRC_ROOT))
    assert not any(os.sep + "fixtures" + os.sep in f for f in files)


# ---------------------------------------------------------------------------
# regression: the promotion hazards the linter surfaced are really fixed
# ---------------------------------------------------------------------------

def test_window_arithmetic_stays_32bit_under_x64():
    from repro.core.jax_state import compact_tracks, fanout_commit

    with jax.enable_x64(True):
        t1 = jnp.asarray(
            np.array([[0.0, 10.0, 30.0, 1e30]], np.float32))
        t2 = jnp.asarray(
            np.array([[5.0, 20.0, 40.0, 1e30]], np.float32))
        valid = jnp.asarray(np.array([[1, 1, 1, 0]], bool))
        ct1, ct2, cv = compact_tracks(t1, t2, valid)
        assert ct1.dtype == jnp.float32 and ct2.dtype == jnp.float32

        shape = (1, 2, 3, 2, 4)
        w1 = jnp.zeros(shape, jnp.float32)
        w2 = jnp.full(shape, 50.0, jnp.float32)
        wv = jnp.ones(shape, bool)
        md = jnp.full((1, 3), 1.0, jnp.float32)
        o1, o2, ov, n_drop, t_drop = fanout_commit(
            w1, w2, wv, md,
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.asarray([10.0], jnp.float32), jnp.asarray([20.0], jnp.float32),
            jnp.asarray([True]),
        )
        assert o1.dtype == jnp.float32 and o2.dtype == jnp.float32
        assert n_drop.dtype == jnp.int32
        assert t_drop.dtype == jnp.float32
