"""chip_smoke.py and the compile-cache helper, as far as a CPU can check
them: the script refuses to run without a TPU, its train and serve phases
pass their count/state assertions at gpt_tiny, and the cache directory is
placed by one helper that defers to ``JAX_COMPILATION_CACHE_DIR``.  The
kernel-presence assertions inside the phases run on the chip only — the
phases skip them by platform, not by a flag."""
import gc
import os
import re
import subprocess
import sys
import time

import jax
import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import chip_smoke  # noqa: E402
from paddle_tpu import sysconfig  # noqa: E402
from paddle_tpu.models import gpt_tiny  # noqa: E402


def test_no_chip_is_a_nonzero_exit_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert time.monotonic() - t0 < 30
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout      # no result line without a chip


def test_train_and_serve_phases_at_gpt_tiny():
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0,
                   use_flash_attention=True, recompute_interval=1)
    model, opt = chip_smoke.train_phase(cfg, batch=2, seq=64, steps=3)
    del opt
    gc.collect()
    eng = chip_smoke.serve_phase(
        model, num_slots=2, page_size=16, max_context=64,
        prompt_lens=(5, 20, 33, 12), new_tokens=4, n_requests=8)
    assert eng.metrics()["completed"] == 8
    eng.close()


def test_mosaic_kernels_reads_the_custom_calls():
    text = ('%0 = stablehlo.custom_call @tpu_custom_call(%a) {backend_config '
            '= "...", kernel_name = "_fwd_kernel"} : ...\n'
            '%1 = stablehlo.custom_call @Sharding(%0) {kernel_name = "no"}\n')
    assert chip_smoke.mosaic_kernels([text]) == {"_fwd_kernel"}


def test_compile_cache_defers_to_the_environment(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert sysconfig.enable_compile_cache() == "/x"
    assert updates == []        # JAX reads the variable; no directory in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(_REPO_ROOT, ".jax_cache")
    assert sysconfig.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_no_other_file_names_a_cache_directory():
    """One helper places the cache: nothing else under paddle_tpu/, tools/,
    bench.py or chip_smoke.py sets the directory, in code or by
    environment."""
    naming = re.compile(r"jax_compilation_cache_dir|JAX_COMPILATION_CACHE_DIR"
                        r"|jax_cache")
    files = [os.path.join(_REPO_ROOT, f) for f in ("bench.py",
                                                   "chip_smoke.py")]
    for top in ("paddle_tpu", "tools"):
        for root, _, names in os.walk(os.path.join(_REPO_ROOT, top)):
            files += [os.path.join(root, n) for n in names
                      if n.endswith(".py")]
    helper = os.path.join(_REPO_ROOT, "paddle_tpu", "sysconfig.py")
    offenders = []
    for path in files:
        if path == helper:
            continue
        with open(path) as f:
            if naming.search(f.read()):
                offenders.append(os.path.relpath(path, _REPO_ROOT))
    assert offenders == []
    with open(os.path.join(_REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_place_index_past_the_last_device_raises():
    from paddle_tpu.core.place import CPUPlace

    n = len(jax.devices("cpu"))
    assert CPUPlace(n - 1).device is jax.devices("cpu")[n - 1]
    with pytest.raises(ValueError, match="valid indices"):
        CPUPlace(n).device
