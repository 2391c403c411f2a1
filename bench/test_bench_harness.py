"""The harness takes additions as data, checks ``BENCHMARK.json``, and
refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fleetbench.spec import NAME_RE, ROOT, UNIT_RE, Benchmark, SpecError  # noqa: E402
from fleetbench.testing import tiny_root  # noqa: E402


def test_bench_committed_benchmark_is_valid():
    b = Benchmark()
    doc = b.doc
    assert [w["name"] for w in doc["workloads"]][0] == \
        "paper_site.weighted4_cong0.3"
    for w in doc["workloads"]:
        assert w["config"] in b.configs
        cell = b.cell(w["name"])
        assert cell.traffic["n_seeds"] > 0
        assert len(w["why"]) <= 200
    for m in b.metrics.values():
        assert NAME_RE.match(m.name) and UNIT_RE.match(m.unit)
        assert callable(m.reader.read)
    names = [w["name"] for w in doc["workloads"]]
    names += [c["name"] for c in doc["configs"]] + list(b.metrics)
    assert all(NAME_RE.match(n) for n in names)
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 2)
    for m in doc["per_layer"]:
        assert m["moves"] == "site_ticks_per_s"


def test_bench_additions_are_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    (root / "bench" / "metrics" / "calls_per_window.py").write_text(
        "def read(ctx):\n    return ctx.calls\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["per_layer"].append({
        "name": "calls_per_window", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "sweep driver",
        "moves": "site_ticks_per_s", "workloads": ["tiny8.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    b = Benchmark(root)
    cell = b.cell("tiny8.tiny")
    assert cell.config["site"]["n_devices"] == 8
    assert cell.config["site"]["n_frames"] == 20
    assert cell.traffic["n_seeds"] == 8
    m = b.metrics["calls_per_window"]
    assert m.reader.read(type("Ctx", (), {"calls": 3})) == 3
    assert [x.name for x in b.metrics_for("tiny8.tiny", "per_layer")][-1] \
        == "calls_per_window"
    assert "calls_per_window" not in [
        x.name for x in b.metrics_for("tiny4.tiny", "per_layer")]
    # the committed files are untouched
    assert "tiny4" not in Benchmark().configs


def _broken(tmp_path, edit):
    root = tiny_root(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    edit(doc, root)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


@pytest.mark.parametrize("edit, message", [
    (lambda d, r: d["workloads"][0].update(config="nowhere"),
     "names no configuration"),
    (lambda d, r: d["per_layer"].append(dict(d["per_layer"][0],
                                             name="no_reader")),
     "no reader"),
    (lambda d, r: d["workloads"][0].update(name="a cell"), "names outside"),
    (lambda d, r: d["per_layer"][0].update(unit="ns per site-tick"),
     "unit"),
    (lambda d, r: d["end_to_end"][0].update(unit="x" * 17), "unit"),
    (lambda d, r: [w.update(chips=4) for w in d["workloads"][:4]],
     "take 4 chips"),
    (lambda d, r: (r / "bench" / "traffic" / "tiny.json").unlink(),
     "missing file"),
])
def test_bench_spec_refuses(tmp_path, edit, message):
    root = _broken(tmp_path, edit)
    with pytest.raises(SpecError, match=message):
        Benchmark(root)


def test_bench_run_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "paper_site.weighted4_cong0.3", "--seed", str(2**33 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert "metrics" not in p.stdout and "correct" not in p.stdout


@pytest.mark.parametrize("key, value", [
    (None, None), ("tasks.lp2.seconds", 16.0), ("engine.max_windows", 32),
    ("site.frame_period_s", 20.0),
])
def test_bench_program_constants_match_the_configuration(key, value):
    from fleetbench.harness import check_program_constants

    conf = Benchmark().configs["paper_site"]
    if key is None:
        check_program_constants(conf)
        return
    conf = json.loads(json.dumps(conf))
    *path, last = key.split(".")
    d = conf
    for p in path:
        d = d[p]
    d[last] = value
    with pytest.raises(RuntimeError, match=key):
        check_program_constants(conf)
