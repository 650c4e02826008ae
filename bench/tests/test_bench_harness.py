"""The harness: ``BENCHMARK.json`` against the files it names, a new cell
and metric found from files alone, and ``bench/run.py`` refusing a
backend that is not a TPU."""

import importlib.util
import json
import re
import shutil
import time

import pytest

import jax

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_has_its_files(bm):
    bench = harness.BENCH
    for cfg in bm["configs"]:
        assert NAME.match(cfg["name"])
        assert (bench.parent / cfg["file"]).is_file()
        data, module = harness.config(cfg["name"])
        assert data["name"] == cfg["name"]
        assert hasattr(module, "least_hbm_bytes")
        assert set(cfg["reduced"]) <= set(data["reduced"])
    for cell in bm["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        tr = harness.traffic(cell["name"])
        step = harness.step_kind(tr["step"])
        data, _ = harness.config(cell["config"])
        assert set(step.NUMBERS) == set(data["guarantees"]["accuracy"])
        assert harness.end_to_end_for(bm, cell)
        assert harness.per_layer_for(bm, cell)
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}


def test_metric_lists_follow_workloads_keys(bm):
    pme = harness.find_cell(bm, "pme-128.step")
    croft = harness.find_cell(bm, "croft-1024.fwd-inv")
    names = {c: [m["name"] for m in harness.per_layer_for(bm, cell)]
             for c, cell in (("pme", pme), ("croft", croft))}
    assert "pallas_ms" in names["pme"] and "pallas_ms" not in names["croft"]
    assert "collective_ms" in names["croft"]
    assert "collective_ms" not in names["pme"]
    with pytest.raises(KeyError):
        harness.find_cell(bm, "no-such-cell")


def test_a_cell_and_a_metric_from_files_alone(tmp_path, tiny):
    """A copy of the benchmark gains a cell (a traffic file) and a
    per-layer metric (a reader file) and the entries naming them; the
    harness runs the new cell, traces the part of its window that the
    traffic names, and reports the new metric with no edit to any file
    it already had."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    traffic = json.loads((root / "bench/traffic/pme-128.step.json")
                         .read_text())
    traffic["pool"] = 4
    traffic["trace_seconds"] = 0.2
    (root / "bench/traffic/pme-128.small-pool.json").write_text(
        json.dumps(traffic))
    (root / "bench/metrics/steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    bm["workloads"].append({"name": "pme-128.small-pool",
                            "config": "pme-128", "traffic": "small-pool",
                            "chips": 1, "why": "a throwaway cell"})
    bm["per_layer"].append({"name": "steps_seen", "unit": "steps",
                            "better": "higher", "source": "program_counter",
                            "layer": "device", "moves": "step_ms",
                            "workloads": ["pme-128.small-pool"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    loaded = harness.load_benchmark(root)
    cell = harness.find_cell(loaded, "pme-128.small-pool")
    assert [m["name"] for m in harness.per_layer_for(loaded, cell)][-1] == \
        "steps_seen"
    res = harness.run_cell(cell, seed=1, seconds=5, trace=True,
                           devices=jax.devices()[:1],
                           t_start=time.perf_counter(), bm=loaded,
                           bench=root / "bench",
                           cfg_override=tiny["pme-128"])
    assert res["correct"]
    assert res["metrics"]["steps_seen"]["value"] == res["attempted"]
    # the traced part of the window is the traffic's trace_seconds
    assert 0.2 <= res["window"]["seconds"] < 1
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _load_run_py():
    spec = importlib.util.spec_from_file_location(
        "bench_run_entry", harness.BENCH / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_run_refuses_a_backend_that_is_not_a_tpu(monkeypatch, capsys):
    import repro.launch.compile_cache as cc

    def touched():
        raise AssertionError("the compile cache was touched")

    monkeypatch.setattr(cc, "use_compile_cache", touched)
    run = _load_run_py()
    rc = run.main(["--workload", "pme-128.step", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "no TPU" in err


def test_run_rejects_an_unknown_workload():
    run = _load_run_py()
    with pytest.raises(KeyError):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])


def test_control_summary_takes_the_worst_program_and_best_control():
    spec = importlib.util.spec_from_file_location(
        "bench_control_entry", harness.BENCH / "control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    table = control.summary([{"e": 1e-7}, {"e": 3e-7}],
                            [{"e": 5e-5}, {"e": 2e-5}])
    assert table["e"]["lower"] == 3e-7 and table["e"]["upper"] == 2e-5
    assert table["e"]["ratio"] == pytest.approx(2e-5 / 3e-7)
