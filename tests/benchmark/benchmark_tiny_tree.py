"""A temporary copy of the benchmark with a tiny configuration, four tiny
cells, three traffic mixes and one per-layer metric with a reader of its own,
ADDED AS NEW FILES AND APPENDED ENTRIES ONLY: no file that was there is edited
(``BENCHMARK.json`` gets entries appended, which is what a later PR does)."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TRAINER = {"learning_rate": 1e-4, "flash_attention": True, "recompute_interval": 1,
           "prefetch_depth": 2}
TRAIN_CHECK = {"layers": 2, "sequences": 2, "loss_abs_tol": 0.01, "grad_rel_tol": 0.03}
ENGINE = {"num_slots": 4, "page_size": 16, "max_context": 64, "prefill_token_budget": 16,
          "prefix_cache": True, "cache_dtype": "bfloat16", "num_pages": 17}
SERVE_CHECK = {"prompt_tokens": 20, "new_tokens": 6, "logit_gap_tol": 0.05}
LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4, "max": 40},
           "answer_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2, "max": 16}}

NEW_FILES = {
    "configs/tiny.json": {
        "name": "tiny", "source": "tests", "builder": "benchmark.configs.gpt_builder",
        "model": {"vocab_size": 1024, "hidden_size": 64, "num_layers": 2, "num_heads": 4,
                  "max_position_embeddings": 128}, "reduced": []},
    "traffic/tiny_train.json": {"name": "tiny_train", "kind": "train", "global_batch": 4,
                                "sequence": 64},
    "traffic/tiny_train4.json": {"name": "tiny_train4", "kind": "train", "global_batch": 4,
                                 "sequence": 64},
    "traffic/tiny_chat.json": {"name": "tiny_chat", "kind": "serve", "mode": "open",
                               **LENGTHS},
    "traffic/tiny_doc.json": {"name": "tiny_doc", "kind": "serve", "mode": "backlog",
                              "backlog_factor": 2, "pool_requests": 20, "stratify_block": 4,
                              "prompt_tokens": {"dist": "lognormal", "median": 30, "sigma": 0.3,
                                                "min": 16, "max": 44},
                              "answer_tokens": {"dist": "constant", "value": 4}},
    "cells/tiny.train.json": {
        "name": "tiny.train", "config": "tiny", "traffic": "tiny_train", "chips": 1,
        "mesh": None, "trainer": TRAINER, "warmup_steps": 2, "trace": {"steps": 2},
        "mosaic_kernels": [], "reference_check": TRAIN_CHECK, "why": "tests"},
    "cells/tiny.train4.json": {
        "name": "tiny.train4", "config": "tiny", "traffic": "tiny_train4", "chips": 4,
        "mesh": {"dp": 2, "mp": 2}, "trainer": TRAINER, "warmup_steps": 2,
        "trace": {"steps": 2}, "mosaic_kernels": [], "reference_check": TRAIN_CHECK,
        "why": "tests"},
    "cells/tiny.chat.json": {
        "name": "tiny.chat", "config": "tiny", "traffic": "tiny_chat", "chips": 1,
        "engine": ENGINE, "rate_per_s": 20.0, "lead_in_s": 0.5, "warm_requests": 3,
        "drain_s_max": 3.0, "trace": {"seconds": 0.5}, "mosaic_kernels": [],
        "reference_check": SERVE_CHECK, "why": "tests"},
    "cells/tiny.doc.json": {
        "name": "tiny.doc", "config": "tiny", "traffic": "tiny_doc", "chips": 1,
        "engine": ENGINE, "lead_in_s": 0.5, "warm_requests": 4, "drain_s_max": 0.0,
        "trace": {"seconds": 0.5}, "mosaic_kernels": [], "reference_check": SERVE_CHECK,
        "why": "tests"},
    "layer_metrics/tiny.longest_step_ms.json": {
        "name": "tiny.longest_step_ms", "layer": "train step", "unit": "ms",
        "moves": "train_tokens_per_s_per_chip",
        "reader": {"file": "tiny_longest_step.py"}},
}
READER_MODULE = '''"""A per-layer reader added beside its metric file."""


def read(params, run, ctx):
    steps = run["clocks"].get("step_s")
    return 1e3 * max(steps) if steps else None
'''
STANDS_FOR = {"tiny.train": "gpt_1p3b.train_b8s1024", "tiny.train4": "gpt_13b_cut.train_dp2mp2",
              "tiny.chat": "gpt_1p3b.serve_chat_r80", "tiny.doc": "gpt_1p3b.serve_doc_sat"}


def build(tmp: str) -> str:
    """Copy the benchmark into ``tmp`` and add the tiny files; returns ``tmp``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, obj in NEW_FILES.items():
        path = os.path.join(bench, rel)
        assert not os.path.exists(path), f"{rel} was there already"
        with open(path, "w") as f:
            json.dump(obj, f, indent=1)
    with open(os.path.join(bench, "layer_metrics", "tiny_longest_step.py"), "w") as f:
        f.write(READER_MODULE)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "tiny", "source": "tests", "reduced": [],
                                "file": "benchmark/configs/tiny.json", "why": "tests"})
    for name, stands_for in STANDS_FOR.items():
        cell = NEW_FILES[f"cells/{name}.json"]
        manifest["workloads"].append({"name": name, "config": "tiny",
                                      "traffic": cell["traffic"], "chips": cell["chips"],
                                      "why": "tests"})
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            listed = metric.get("workloads")
            if listed is not None and stands_for in listed:
                listed.append(name)
            if name == "tiny.train4" and listed is not None \
                    and "gpt_1p3b.train_b8s1024" in listed and name not in listed:
                listed.append(name)
    manifest["per_layer"].append({
        "name": "tiny.longest_step_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["tiny.train"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return tmp
