"""The manifest is valid, every cell's files resolve by name, and a cell, a
configuration, a traffic mix and a per-layer metric added as new files are
picked up with no edit to a file that was there."""
import filecmp
import json
import os
import re
import subprocess
import sys
import time

import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.harness import manifest as bm  # noqa: E402


@pytest.fixture(scope="module")
def manifest():
    return bm.load_manifest()


def test_manifest_is_valid(manifest):
    assert bm.validate(manifest) == []
    assert len(json.dumps(manifest)) < 64 * 1024


def test_validator_sees_the_faults_it_is_there_for(manifest):
    broken = json.loads(json.dumps(manifest))
    broken["workloads"][0]["chips"] = 4
    broken["workloads"][1]["chips"] = 4
    broken["per_layer"][0]["unit"] = "tokens per second"
    broken["configs"][1]["reduced"] = ["has space"]
    chat_only = next(m for m in broken["per_layer"] if m["name"] == "gen.lateness_p95_ms")
    chat_only["moves"] = "train_tokens_per_s_per_chip"
    faults = "\n".join(bm.validate(broken))
    for expected in ("four chips", "unit", "not a valid name", "is reported in"):
        assert expected in faults, (expected, faults)


@pytest.mark.parametrize("cell", [w["name"] for w in bm.load_manifest()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    ctx = bm.resolve_cell(cell)
    assert ctx["cell"]["name"] == cell
    assert ctx["cell"]["why"] == ctx["entry"]["why"]
    assert ctx["cell"]["chips"] == ctx["entry"]["chips"]
    assert ctx["config"]["name"] == ctx["entry"]["config"] == ctx["cell"]["config"]
    assert ctx["traffic"]["name"] == ctx["entry"]["traffic"] == ctx["cell"]["traffic"]
    assert ctx["traffic"]["kind"] in ("train", "serve")
    names = [m["name"] for m in ctx["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert ctx["per_layer"] and all("reader" in m for m in ctx["per_layer"])
    assert os.path.exists(os.path.join(tiny.REPO, "benchmark", "runners",
                                       ctx["traffic"]["kind"] + ".py"))


def test_layer_metric_files_agree_with_the_manifest(manifest):
    from benchmark.harness import readers

    for m in manifest["per_layer"]:
        with open(os.path.join(tiny.REPO, "benchmark", "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) == \
            (m["name"], m["layer"], m["unit"], m["moves"])
        assert spec["reader"].get("kind") in readers.VOCABULARY or "file" in spec["reader"]


def test_reduced_names_only_depth(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            on_disk = json.load(f)
        assert on_disk["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key == "num_layers" and not key.endswith(("_dim", "_rank", "_size"))


def test_added_files_are_picked_up_without_editing_any(tmp_path):
    root = tiny.build(str(tmp_path))
    # no file that was there differs from the repo's
    for rel_dir, _, names in os.walk(os.path.join(tiny.REPO, "benchmark")):
        if "__pycache__" in rel_dir:
            continue
        for n in names:
            src = os.path.join(rel_dir, n)
            dst = os.path.join(root, os.path.relpath(src, tiny.REPO))
            assert filecmp.cmp(src, dst, shallow=False), src
    assert bm.validate(bm.load_manifest(root)) == []
    ctx = bm.resolve_cell("tiny.train", root=root)
    assert ctx["config"]["model"]["hidden_size"] == 64
    assert ctx["traffic"]["global_batch"] == 4
    assert "tiny.longest_step_ms" in [m["name"] for m in ctx["per_layer"]]
    assert "train_tokens_per_s_per_chip" in [m["name"] for m in ctx["end_to_end"]]
    chat = bm.resolve_cell("tiny.chat", root=root)
    assert [m["name"] for m in chat["end_to_end"]] == \
        ["setup_s", "serve_itl_p95_ms"]


def test_no_cell_or_configuration_is_named_in_harness_code(manifest):
    names = [w["name"] for w in manifest["workloads"]] + [c["name"] for c in manifest["configs"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    for rel_dir, _, files in os.walk(os.path.join(tiny.REPO, "benchmark")):
        for n in files:
            if not n.endswith(".py"):
                continue
            with open(os.path.join(rel_dir, n)) as f:
                text = f.read()
            for name in names:
                assert not re.search(r"\b" + re.escape(name) + r"\b", text), (n, name)


def test_no_chip_is_a_nonzero_exit_that_names_the_device(manifest):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "benchmark", "run.py"), "--workload",
         manifest["workloads"][0]["name"], "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=120, cwd=tiny.REPO)
    assert time.monotonic() - t0 < 60
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout
