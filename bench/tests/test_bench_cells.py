"""Cells, configurations and metrics are found by name; each cell's
generator and window run end to end at a tiny size on the CPU; and
``correct`` comes out false for the lower-precision control and for
each planted fault, under the cells' own limits."""
import copy
import importlib
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

SPEC = harness.benchmark_spec(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]
#: protocol keys whose first 4-step chunk is 0, 0, 1, 1 at p = 0.5 / 0.1
TINY_KEYS = {0.5: 2, 0.1: 408}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
#: what every ``bench/models/<model>.py`` gives
MODEL_FUNCTIONS = ("program_config", "reference_spec", "loss",
                   "train_flops_per_token", "tiny")


def test_every_cell_and_metric_of_the_benchmark_has_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["why"] == w["why"]
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config_spec = json.load(f)
        assert config_spec["reduced"] == c["reduced"]
        model = harness.model_of(config_spec)
        for fn in MODEL_FUNCTIONS:
            assert callable(getattr(model, fn)), (c["name"], fn)
    for m in SPEC["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_a_new_cell_and_metric_are_found_by_their_files(tmp_path):
    for d in ("workloads", "configs", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "m.json").write_text('{"hidden_size": 8}')
    (tmp_path / "workloads" / "m.new-mix.json").write_text(json.dumps(
        {"config": "m", "kind": "train", "chips": 1, "params": {}}))
    (tmp_path / "metrics" / "twice.train.py").write_text(
        "def read(rec):\n    return 2 * rec['x']\n")
    cell = harness.load_cell("m.new-mix", bench_dir=str(tmp_path))
    assert cell["config_spec"]["hidden_size"] == 8
    assert harness.reader("twice.train", bench_dir=str(tmp_path))(
        {"x": 3}) == 6
    spec = {"end_to_end": [{"name": "train_tokens_per_s"}],
            "per_layer": [{"name": "twice.train", "moves":
                           "train_tokens_per_s",
                           "workloads": ["m.new-mix"]},
                          {"name": "all.train",
                           "moves": "train_tokens_per_s"},
                          {"name": "other", "moves": "train_tokens_per_s",
                           "workloads": ["x"]}]}
    assert [m["name"] for m in harness.metrics_of(
        spec, "m.new-mix", "per_layer")] == ["twice.train", "all.train"]


def tiny(name: str, bench_dir: str = None) -> dict:
    """The cell at a CPU size: widths (its model's ``tiny``), batch,
    sequence and chunk cut, everything else (codecs, p, limits) as the
    cell states it."""
    cell = copy.deepcopy(harness.load_cell(name, bench_dir))
    spec = cell["config_spec"]
    spec.update(harness.model_of(spec, bench_dir).tiny(spec))
    P = cell["params"]
    P.update(batch=2, seq=16, chunk=4,
             protocol_key=TINY_KEYS[P["p"]])
    return cell


def run_cell(cell, seconds=0.3, seed=2 ** 31 + 7):
    kind = importlib.import_module(f"bench.kinds.{cell['kind']}")
    return kind.run(cell, types.SimpleNamespace(
        seed=seed, seconds=seconds, trace=0), time.time(), DEVICE, SPEC)


def correct(result, checks):
    return result["correct"] and all(c["ok"] for c in checks)


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_runs_whole_chunks_and_is_correct(name):
    cell = tiny(name)
    t = time.time()
    result, checks = run_cell(cell, seconds=0.3)
    assert correct(result, checks), checks
    assert result["attempted"] % cell["params"]["chunk"] == 0
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert 0 < result["metrics"]["setup_s"]["value"] < time.time() - t
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def test_a_new_model_is_found_by_its_files(tmp_path):
    """A model module, a configuration and a cell, written as new files
    into a bench directory of their own, run to ``correct`` with no edit
    to any file the benchmark has: the harness finds the model by the
    configuration's ``"model"`` key, which names no module under
    ``bench/models``."""
    for d in ("models", "configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "models" / "decoder_copy.py").write_text(
        "from bench.models.decoder import (  # noqa: F401\n"
        "    program_config, reference_spec, train_flops_per_token, tiny)\n"
        "from bench.reference.lm import loss  # noqa: F401\n")
    assert not os.path.exists(os.path.join(harness.BENCH_DIR, "models",
                                           "decoder_copy.py"))
    src = harness.load_cell(CELLS[0])
    config_spec = dict(src["config_spec"], name="new-model",
                       model="decoder_copy")
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps(config_spec))
    with open(os.path.join(harness.BENCH_DIR, "workloads",
                           f"{CELLS[0]}.json")) as f:
        cell_file = dict(json.load(f), config="new-model")
    (tmp_path / "workloads" / "new-model.mix.json").write_text(
        json.dumps(cell_file))

    cell = tiny("new-model.mix", bench_dir=str(tmp_path))
    model = harness.model_of(cell["config_spec"], str(tmp_path))
    assert os.path.dirname(model.__file__) == str(tmp_path / "models")
    for fn in MODEL_FUNCTIONS:
        assert callable(getattr(model, fn)), fn
    result, checks = run_cell(cell, seconds=0.1)
    assert correct(result, checks), checks
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


FAULTS = ["unchanged", "halfbatch", "altered", "cached"]


def _fault_cases():
    """Each fault on each cell; the first cell's cases keep the fault's
    name as their id."""
    return [pytest.param(cell, fault, id=fault if cell == CELLS[0]
                         else f"{cell}-{fault}")
            for cell in CELLS for fault in FAULTS]


@pytest.mark.parametrize("name, fault", _fault_cases())
def test_a_planted_fault_makes_the_run_incorrect(name, fault, monkeypatch):
    from bench import readings
    from bench.kinds import train
    for owner, attr, fn in readings.planted(fault, train):
        monkeypatch.setattr(owner, attr, fn)
    result, checks = run_cell(tiny(name), seconds=0.1)
    assert not correct(result, checks), checks


def test_the_bfloat16_control_fails_the_limits():
    from bench import readings
    for name in CELLS:
        cell = tiny(name)
        rows = readings.readings(cell, [3], ["control"])
        limits = cell["limits"]
        assert any(rows[0][k] > v for k, v in limits.items()), (name, rows)


def test_the_command_fails_without_a_chip_and_prints_no_result(tmp_path):
    import shutil
    import subprocess
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""
