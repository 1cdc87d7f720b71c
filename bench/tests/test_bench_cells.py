"""Cells, configurations and metrics are found by name; each cell's
generator and window run end to end at a tiny size on the CPU; and
``correct`` comes out false for the lower-precision control and for
each planted fault, under the cells' own limits.

A cell on one chip runs in this process.  A cell on more than one chip
runs, with all its faults and its control, in one process of its own on
as many forced CPU devices as it asks for (``tiny_cells.py``), so that
its mesh really spans them; a four-chip cell written as new files is
one of the cases."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.tests.tiny_cells import (correct, run_cell, tiny,  # noqa: E402
                                    CONTROL_SEED)

SPEC = harness.benchmark_spec(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]
CHIPS = {w["name"]: w["chips"] for w in SPEC["workloads"]}
#: what every ``bench/models/<model>.py`` gives
MODEL_FUNCTIONS = ("program_config", "reference_spec", "loss",
                   "train_flops_per_token", "tiny")
#: a four-chip cell that the tests write as new files (``four_chip_dir``)
FILES_CELL = "new-model.fl-comm-4chip"
TINY_CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tiny_cells.py")


@pytest.fixture(scope="module")
def four_chip_dir(tmp_path_factory):
    """A cell on four chips, its configuration and the model it names,
    written as new files into a bench directory of their own: the first
    cell's configuration and traffic with 4 clients, one per chip, and a
    model module that is the existing ``decoder``."""
    d = tmp_path_factory.mktemp("bench")
    for sub in ("models", "configs", "workloads"):
        (d / sub).mkdir()
    (d / "models" / "decoder.py").write_text(
        "from bench.models.decoder import (  # noqa: F401\n"
        "    program_config, reference_spec, train_flops_per_token, tiny)\n"
        "from bench.reference.lm import loss  # noqa: F401\n")
    src = harness.load_cell(CELLS[0])
    (d / "configs" / "new-model.json").write_text(json.dumps(
        dict(src["config_spec"], name="new-model")))
    with open(os.path.join(harness.BENCH_DIR, "workloads",
                           f"{CELLS[0]}.json")) as f:
        cell_file = json.load(f)
    cell_file.update(config="new-model", chips=4, why="one client per chip")
    cell_file["params"].update(clients=4)
    (d / "workloads" / f"{FILES_CELL}.json").write_text(json.dumps(cell_file))
    return str(d)


@pytest.fixture(scope="module")
def on_devices():
    """``report(name, bench_dir)``: what ``tiny_cells.py`` prints for a
    multi-chip cell, run once per cell in a process of its own on as
    many forced CPU devices as the cell asks for."""
    reports = {}

    def report(name, bench_dir=None):
        if (name, bench_dir) not in reports:
            chips = harness.load_cell(name, bench_dir)["chips"]
            flags = f"--xla_force_host_platform_device_count={chips}"
            env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
                f for f in (os.environ.get("XLA_FLAGS"), flags) if f))
            out = subprocess.run(
                [sys.executable, TINY_CELLS, "--workload", name]
                + (["--bench-dir", bench_dir] if bench_dir else []),
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=900)
            assert out.returncode == 0, out.stderr[-4000:]
            reports[name, bench_dir] = json.loads(
                out.stdout.strip().splitlines()[-1])
        return reports[name, bench_dir]
    return report


def _bench_dir(name, request):
    """The bench directory of a case: the files cell lies in its own."""
    return request.getfixturevalue("four_chip_dir") \
        if name == FILES_CELL else None


def outcome(name, variant, request):
    """(result, checks, wall seconds) of the cell's tiny copy, sound or
    with a fault planted: here on one chip, in a process of its own on
    more."""
    from bench import readings
    from bench.kinds import train
    bench_dir = _bench_dir(name, request)
    cell = tiny(name, bench_dir)
    if cell["chips"] > 1:
        return request.getfixturevalue("on_devices")(
            name, bench_dir)["runs"][variant]
    if variant == "sound":
        return run_cell(cell, seconds=0.3)
    with readings.planting(variant, train):
        return run_cell(cell, seconds=0.1)


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


@pytest.mark.parametrize("name", CELLS + [FILES_CELL])
def test_tiny_cell_runs_whole_chunks_and_is_correct(name, request):
    result, checks, wall_s = outcome(name, "sound", request)
    assert correct(result, checks), checks
    assert result["attempted"] % 4 == 0        # whole chunks of the tiny size
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert 0 < result["metrics"]["setup_s"]["value"] < wall_s
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
    result, checks, _ = run_cell(cell, seconds=0.1)
    assert correct(result, checks), checks
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def _fault_cases():
    """Each fault its chips can have on each cell, and on the four-chip
    cell written as files; the first cell's cases keep the fault's name
    as their id."""
    from bench import readings
    chips = dict(CHIPS, **{FILES_CELL: 4})
    return [pytest.param(cell, fault, id=fault if cell == CELLS[0]
                         else f"{cell}-{fault}")
            for cell in CELLS + [FILES_CELL]
            for fault in readings.faults_of({"chips": chips[cell]})]


@pytest.mark.parametrize("name, fault", _fault_cases())
def test_a_planted_fault_makes_the_run_incorrect(name, fault, request):
    result, checks, _ = outcome(name, fault, request)
    assert not correct(result, checks), checks


def _control(name, request):
    """The control's readings of the cell's tiny copy, and its limits."""
    from bench import readings
    bench_dir = _bench_dir(name, request)
    cell = tiny(name, bench_dir)
    if cell["chips"] > 1:
        row = request.getfixturevalue("on_devices")(
            name, bench_dir)["control"]
    else:
        row = readings.readings(cell, [CONTROL_SEED], ["control"])[0]
    return row, cell["limits"]


def test_the_bfloat16_control_fails_the_limits(request):
    for name in CELLS:
        row, limits = _control(name, request)
        assert any(row[k] > v for k, v in limits.items()), (name, row)


def test_the_bfloat16_control_fails_a_four_chip_cell_added_as_files(
        request):
    row, limits = _control(FILES_CELL, request)
    assert any(row[k] > v for k, v in limits.items()), row


def test_a_four_chip_cell_holds_one_client_per_chip_and_donates_its_state(
        four_chip_dir, on_devices):
    """After the first chunk every params leaf spans the four devices,
    one client on each; the compiled chunk gathers across them and
    aliases each state input to its output, and a call consumes the
    state it was given."""
    placed = on_devices(FILES_CELL, four_chip_dir)["placement"]
    assert placed["spans"] == [[[d, 1] for d in range(4)]] * \
        placed["param_leaves"]
    assert placed["all_gather"]
    assert placed["aliased"] >= placed["param_leaves"]
    assert placed["donated"]


def test_a_four_chip_cells_weights_are_the_stacked_clients_numbers(
        four_chip_dir, on_devices):
    """The seed's clients made sharded over the mesh, and one at a time
    on the device that holds each, are the stacked clients bit for
    bit."""
    assert on_devices(FILES_CELL, four_chip_dir)["weights"] == {
        "sharded": True, "one_at_a_time": True, "on_its_device": True}


def test_the_sharded_chunk_on_one_device_reads_as_the_stacked_chunk():
    """On a one-device client mesh the sharded engine's first chunk, with
    its state donated, gives the stacked chunk's readings bit for bit
    (the engine's own equivalence, here at the cell's flat transport and
    codecs)."""
    import numpy as np
    from bench.kinds import train
    from repro.launch.mesh import make_client_mesh
    cell = tiny(CELLS[0])
    stacked = train.Job(cell, 11)
    sharded = train.Job(cell, 11, roll=train.program_sharded_chunk(
        train.program_grad_fn(stacked.cfg), stacked.up, stacked.down,
        stacked.chunk_len, cell["params"]["local_steps"],
        make_client_mesh(1)))
    (_, a), (_, b) = stacked.first_chunk(), sharded.first_chunk()
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert stacked.mismatches == sharded.mismatches == 0


def test_a_cell_whose_clients_do_not_divide_over_its_chips_is_refused():
    from bench.kinds import train
    cell = tiny(CELLS[0])
    cell.update(chips=4)
    cell["params"].update(clients=6)
    with pytest.raises(ValueError, match="do not divide"):
        train.Job(cell, 1)


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
