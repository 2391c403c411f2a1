"""Tracing from inside the program (src/repro/obs/profile.py and the
named scopes of fleet/engine.py):

- the tick phases partition the segment scan's body: every op traced
  inside a tick lies under exactly one ``tick/<phase>`` scope, and the
  benchmark's compaction and placement patterns still find their ops,
  now inside a phase;
- ``span`` is a profiler annotation on the trace's host planes and a
  PhaseTimer span at once; ``count`` is a no-op with no timer active;
- ``$REPRO_PROFILE_DIR`` traces a whole sweep with its host spans,
  sharded or not;
- a PhaseTimer changes no result, sharded or not.

Fleet runs share the (B, F, Dev) signature of tests/test_obs.py.
"""

import dataclasses
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import (FleetParams, SweepConfig, fleet_run, make_fleet,
                         make_workload, run_sweep)
from repro.fleet import engine
from repro.obs import profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from fleetbench.layers import COMPACTION, PLACEMENT  # noqa: E402

B, F, DEV = 8, 8, 4
PARAMS = FleetParams(n_devices=DEV)
PHASES = {"housekeeping", "requeue", "hp", "realloc", "lp", "mask"}
#: what the scan adds around its body: the induction variable, the
#: slices of ``xs``, and the call of the body (its argument tuple, the
#: unpacking of its result, constants the compiler hoists to it).
LOOP_BOOKKEEPING = {"add", "dynamic_slice", "closed_call"}
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _wl(seed=0):
    return make_workload("weighted2", B, F, DEV, seed=seed, congestion=0.3)


def _run(params, wl=None):
    wl = wl or _wl()
    return fleet_run(make_fleet(B, DEV, requeue_slots=params.requeue_slots),
                     wl.values, wl.bw_scale, params=params)


@pytest.fixture(scope="module")
def segment():
    """The op names of the compiled segment program at the shared
    signature: the names a profiler trace reads."""
    wl = _wl()
    args = (engine.initial_carry(make_fleet(B, DEV)),
            jnp.asarray(wl.values, jnp.int32),
            jnp.asarray(wl.bw_scale, jnp.float32),
            jnp.int32(0), jnp.int32(F))
    compiled = engine._run_segment.lower(*args, params=PARAMS).compile()
    return OP_NAME.findall(compiled.as_text())


def test_tick_phases_partition_the_scan_body(segment):
    names = segment
    (prefix,) = {n.split("/while/body/")[0] for n in names
                 if "/while/body/" in n}
    body = prefix + "/while/body/"
    seen = set()
    for n in names:
        if not n.startswith(body):
            continue
        rest = n[len(body):]
        if not rest.startswith("closed_call/"):
            assert rest in LOOP_BOOKKEEPING, n
            continue
        phases = re.findall(r"(?:^|/)tick/([^/]+)", rest)
        assert len(phases) == 1 and phases[0] in PHASES, n
        seen.add(phases[0])
    assert seen == PHASES


def test_layer_patterns_match_inside_a_phase(segment):
    names = segment
    compaction = [n for n in names if re.search(COMPACTION, n)]
    placement = [n for n in names if re.search(PLACEMENT, n)]
    assert compaction and placement
    # (a reducer's own computation names its ops from the scope down)
    assert all(re.search(r"(^|/)tick/housekeeping/", n) for n in compaction)
    assert all(re.search(r"(^|/)tick/[a-z]+/", n) for n in placement)
    # no scope of the program looks like a name the patterns key on
    scopes = {s for n in names for s in re.findall(
        r"(?:tick|placement|sweep)/[^/]+", n)}
    assert scopes and not any(w in s for s in scopes
                              for w in ("cond", "branch_", "fused_place"))


def test_span_is_a_profiler_annotation_and_a_timer_span(tmp_path):
    with profile.PhaseTimer() as t:
        with jax.profiler.trace(str(tmp_path)):
            with profile.span("obs/traced_span"):
                jnp.ones(4).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert "obs/traced_span" in host
    assert t.summary()["obs/traced_span"]["count"] == 1


def test_count_adds_only_while_a_timer_is_active(tmp_path):
    profile.count("obs/inactive", 5)          # no timer: a no-op
    with profile.PhaseTimer() as outer:
        profile.count("obs/n", 2)
        with profile.PhaseTimer() as inner:
            profile.count("obs/n", 3)
    profile.count("obs/n", 7)                 # both exited
    assert outer.counts == {"obs/n": 5} and inner.counts == {"obs/n": 3}
    assert outer.summary()["obs/n"] == {"total": 5}
    payload = outer.save(str(tmp_path / "p.json"))
    assert payload["phases"]["obs/n"] == {"total": 5}


@pytest.mark.parametrize("shards", [0, 1])
def test_profile_dir_traces_the_whole_sweep(shards, tmp_path, monkeypatch):
    monkeypatch.setenv(profile.ENV_VAR, str(tmp_path))
    cfg = SweepConfig(scenarios=("weighted2",), congestion_levels=(0.3,),
                      n_seeds=B, n_frames=F, n_devices=DEV, batch_size=B,
                      params=PARAMS, mesh_shards=shards)
    with profile.PhaseTimer() as t:
        run_sweep(cfg)
    # one trace for the whole sweep (fleet_run's own hook nests inside)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    spans = {"sweep/workload", "sweep/batch", "sweep/make_fleet",
             "fleet/put", "fleet/segment",
             "sweep/reduce" if shards else "sweep/collect"}
    if not shards:
        spans.add("sweep/summarize")
    assert spans <= host and spans <= set(t.summary())


def _results(params):
    state, stats = _run(params)
    return jax.tree_util.tree_map(np.asarray, (state, stats))


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(x, y)


@pytest.mark.parametrize("shards", [0, 1])
def test_phase_timer_leaves_results_bit_identical(shards):
    p = dataclasses.replace(PARAMS, mesh_shards=shards)
    plain = _results(p)
    with profile.PhaseTimer() as t:
        timed = _results(p)
    _assert_trees_equal(plain, timed)
    assert {"fleet/put", "fleet/segment"} <= set(t.summary())
    # the sharded run agrees with the unsharded one
    _assert_trees_equal(plain, _results(PARAMS))


CHILD = textwrap.dedent("""
    import dataclasses, json
    import jax, numpy as np
    from repro.fleet import FleetParams, fleet_run, make_fleet, make_workload
    from repro.obs import profile

    def run(p):
        wl = make_workload("weighted2", 8, 8, 4, seed=0, congestion=0.3)
        out = fleet_run(make_fleet(8, 4), wl.values, wl.bw_scale, params=p)
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]

    base = FleetParams(n_devices=4)
    plain = run(base)
    two = dataclasses.replace(base, mesh_shards=2)
    untimed = run(two)
    with profile.PhaseTimer() as t:
        timed = run(two)
    same = lambda a, b: all(np.array_equal(x, y) for x, y in zip(a, b))
    print(json.dumps({"devices": jax.device_count(),
                      "timer": same(untimed, timed),
                      "sharded": same(plain, untimed),
                      "spans": sorted(t.summary())}))
""")


def test_phase_timer_leaves_a_two_chip_mesh_bit_identical():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r == {"devices": 2, "timer": True, "sharded": True,
                 "spans": ["fleet/put", "fleet/segment"]}
