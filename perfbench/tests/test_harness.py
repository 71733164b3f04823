"""A whole run of a cell on the CPU at a small size, with the look for a
chip skipped: sound, it comes out correct; with the timed path broken
underneath, or the lower-precision control in the program's place, it
does not. Also: without a TPU a run prints no result, and a new cell
needs only new files and entries."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
import qcnn

WORKLOAD = "resnet18.b1.z020"
SEED = 2 ** 33 + 5  # wider than 32 bits: seeds are any whole number
IN_HW = 32          # the cell's network at 32x32 instead of 224x224
SECONDS = 0.5


def small_cell(workload=WORKLOAD):
    cell = harness.load_cell(workload)
    return dataclasses.replace(cell, config=dict(cell.config, in_hw=IN_HW))


def run_small(hook=None, workload=WORKLOAD):
    return harness.run(workload, SEED, SECONDS, False, time.perf_counter(),
                       require_tpu=False, cell=small_cell(workload),
                       system_hook=hook)


def test_sound_run_is_correct_and_complete():
    result, notes = run_small()
    assert result["correct"] is True, notes
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["mismatch_share"]["value"] == 0.0
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert set(result["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert notes[-1].startswith("check mismatch_share:")


def _break_answers(system):
    """Fault: every answer altered where it is produced."""
    run = system.ex.run

    def altered(x):
        y = run(x)
        return y.at[0, 0].add(1.0 + abs(y).max())
    system.ex.run = altered


def _break_one_layer(system):
    """Fault: one layer's output altered by one code's worth."""
    run_layer = system.ex.run_layer

    def altered(index, x):
        y = run_layer(index, x)
        return y.at[0, 0].add(abs(y).max() / 7) if index == 3 else y
    system.ex.run_layer = altered


def _control(system):
    """The control: the reference in bfloat16 in the program's place."""
    import jax.numpy as jnp
    ws, ss = harness.full_weights(system.weights)
    system.ex.run = lambda x: qcnn.forward(system.layers, ws, ss, x, jnp.bfloat16)


@pytest.mark.parametrize("hook", [_break_answers, _break_one_layer, _control],
                         ids=["answer_altered", "layer_altered", "bf16_control"])
def test_broken_path_is_not_correct(hook):
    result, notes = run_small(hook)
    assert result["correct"] is False, notes
    check = result["checks"]["mismatch_share"]
    assert check["value"] > check["limit"]


def test_no_tpu_no_result(capsys):
    import run
    rc = run.main(["--workload", WORKLOAD, "--seed", "1", "--seconds", "0.1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A copy of the benchmark gains a mix, a cell and a per-layer metric
    without an edit to any file it had, and a run of the new cell (on the
    CPU, small) reads them."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(harness.ROOT / "src", root / "src")
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*") if p.is_file()}
    traffic = json.loads((root / "perfbench/traffic/b1.z020.json").read_text())
    (root / "perfbench/traffic/b1.z045.json").write_text(
        json.dumps(dict(traffic, fpga_target="XC7Z045")))
    (root / "perfbench/checks/resnet18.b1.z045.json").write_text(
        (root / "perfbench/checks/resnet18.b1.z020.json").read_text())
    (root / "perfbench/metrics/executor.layers_per_image.py").write_text(
        "def read(ctx):\n    return sum(ctx.counters.values()) / ctx.images\n")
    spec["workloads"].append({"name": "resnet18.b1.z045", "config": "resnet18",
                              "traffic": "b1.z045", "chips": 1, "why": "rehearsal"})
    spec["per_layer"].append({"name": "executor.layers_per_image", "unit": "layers",
                              "better": "lower", "source": "program_counter",
                              "layer": "executor", "moves": "images_per_s",
                              "workloads": ["resnet18.b1.z045"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing the benchmark had was edited
    script = (
        "import dataclasses, json, sys, time\n"
        "sys.path[:0] = ['perfbench', 'src']\n"
        "import harness\n"
        "cell = harness.load_cell('resnet18.b1.z045')\n"
        f"cell = dataclasses.replace(cell, config=dict(cell.config, in_hw={IN_HW}))\n"
        "ctx = {}\n"
        "def hook(system):\n"
        "    ctx['layers'] = len(system.prog.layers)\n"
        "res, notes = harness.run('resnet18.b1.z045', 7, 0.2, False, "
        "time.perf_counter(), require_tpu=False, cell=cell, system_hook=hook)\n"
        "print(json.dumps([res['correct'], cell.traffic['fpga_target'], ctx, "
        "[m['name'] for m in cell.per_layer]]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    correct, target, ctx, per_layer = json.loads(out.stdout.strip().splitlines()[-1])
    assert correct is True
    assert target == "XC7Z045"
    assert per_layer[-1] == "executor.layers_per_image"
    assert ctx["layers"] == 21
