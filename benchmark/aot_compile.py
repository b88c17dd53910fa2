#!/usr/bin/env python3
"""Compile a cell's whole step program for the DESCRIBED v5e, with no chip:

    JAX_PLATFORMS=cpu python3 benchmark/aot_compile.py --workload <cell> [--layers N] [--pages N]

What the chip's compiler refuses (a Mosaic kernel, a program over the chip's
memory) it refuses here, and ``memory_analysis()`` gives the bytes a device
holds: how the pool size and the cut depth of a configuration are found
before any chip time.  The program builds its state on the CPU's devices; the
first dispatch is stopped before anything runs, and the traced program is
lowered for the described devices instead.  Nothing here measures anything: a
compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY_ENV = {"TPU_LOG_DIR": "disabled", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
                "TPU_WORKER_HOSTNAMES": "localhost", "TPU_SKIP_MDS_QUERY": "1"}


def describe_topology(name: str = "v5e:2x2"):
    from jax.experimental import topologies

    for key, value in TOPOLOGY_ENV.items():
        os.environ.setdefault(key, value)
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


class _Stopped(BaseException):
    """Raised in place of the first dispatch: the program is traced, not run."""


@contextlib.contextmanager
def _traced_not_run():
    """Inside: kernels take their TPU path and a compiled step stops before it
    runs.  Restores both on the way out."""
    from paddle_tpu.jit import api as jit_api
    from paddle_tpu.ops.pallas_kernels import flash_attention, ragged_paged_attention

    def stop(self, entry, arg_tensors):
        raise _Stopped()

    saved = (jit_api.StaticFunction._run_compiled, flash_attention._on_tpu,
             ragged_paged_attention._on_tpu)
    jit_api.StaticFunction._run_compiled = stop
    flash_attention._on_tpu = ragged_paged_attention._on_tpu = lambda: True
    try:
        yield
    finally:
        (jit_api.StaticFunction._run_compiled, flash_attention._on_tpu,
         ragged_paged_attention._on_tpu) = saved


def _entry(static_fn):
    return next(e for e in static_fn._cache.values() if e.jitted is not None)


def _report(compiled) -> dict:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    text = compiled.as_text()
    return {"argument_bytes": ma.argument_size_in_bytes, "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes, "output_bytes": ma.output_size_in_bytes,
            "device_bytes": total, "mosaic_calls": text.count("tpu_custom_call"),
            "collectives": {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                            for k in ("all-gather", "all-reduce", "reduce-scatter",
                                      "collective-permute")}}


def serve_step(ctx: dict, topo) -> dict:
    """The fused (greedy) serving step of a ``kind: serve`` cell on one chip."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.serving import ServingEngine

    one = SingleDeviceSharding(topo.devices[0])
    builder = importlib.import_module(ctx["config"]["builder"])
    model = builder.build_model(ctx["config"], seed=0)
    engine = ServingEngine(model, **ctx["cell"]["engine"])
    try:
        with _traced_not_run():
            engine.submit(np.arange(64), 4)
            try:
                engine.step()
            except _Stopped:
                pass
            entry = _entry(engine._fused_greedy)

            def struct(v):
                return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype, sharding=one)

            compiled = entry.jitted.lower(
                [struct(s) for s in entry.arg_structs],
                [struct(t._value) for t in entry.mut_caps],
                [struct(t._value) for t in entry.ro_caps]).compile()
    finally:
        engine.close()
    return _report(compiled)


def train_step(ctx: dict, topo) -> dict:
    """The fused train step of a ``kind: train`` cell, on its mesh if it has one."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from paddle_tpu.distributed import mesh as dmesh

    from benchmark.generators.requests import train_batch
    from benchmark.runners import train as runner

    chips = ctx["entry"]["chips"]
    builder = importlib.import_module(ctx["config"]["builder"])
    try:
        cpu_mesh, sharding = runner._mesh_and_sharding(ctx["cell"], jax.devices()[:chips])
        model = builder.build_model(ctx["config"], seed=0, trainer=ctx["cell"]["trainer"])
        step, opt = runner._train_step(model, ctx["cell"]["trainer"])
        ids, labels = train_batch(ctx["traffic"], seed=0, step=0,
                                  vocab=ctx["config"]["model"]["vocab_size"])
        batch = runner.place_batch((ids, labels), sharding)
        with _traced_not_run():
            try:
                step(*batch)
            except _Stopped:
                pass
            entry = _entry(step._step_fn)
            if cpu_mesh is None:
                place = lambda sh: SingleDeviceSharding(topo.devices[0])  # noqa: E731
                batch_sharding = place(None)
            else:
                mesh = Mesh(np.array(topo.devices[:chips]).reshape(cpu_mesh.devices.shape),
                            cpu_mesh.axis_names)
                dmesh.set_mesh(mesh)    # what the trace reads: shard_map, constraints

                def place(sh):
                    return NamedSharding(mesh, sh.spec if isinstance(sh, NamedSharding) else P())

                batch_sharding = NamedSharding(mesh, sharding.spec)
                opt._param_layouts = {k: place(v) for k, v in
                                      getattr(opt, "_param_layouts", {}).items()}

            def struct(t):
                v = t._value
                return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype, sharding=place(v.sharding))

            compiled = entry.jitted.lower(
                [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=batch_sharding)
                 for s in entry.arg_structs],
                [struct(t) for t in entry.mut_caps],
                [struct(t) for t in entry.ro_caps]).compile()
    finally:
        dmesh.set_mesh(None)
    return _report(compiled)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, help="compile at this depth instead of the file's")
    ap.add_argument("--pages", type=int, help="compile with this pool instead of the file's")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("set JAX_PLATFORMS=cpu: the state is built on the CPU's devices", file=sys.stderr)
        return 2
    from benchmark.harness import manifest

    ctx = manifest.resolve_cell(args.workload)
    if args.layers:
        ctx["config"]["model"]["num_layers"] = args.layers
    if args.pages:
        ctx["cell"]["engine"]["num_pages"] = args.pages
    kind = ctx["traffic"]["kind"]
    report = {"serve": serve_step, "train": train_step}[kind](ctx, describe_topology())
    print(json.dumps({"workload": args.workload, "layers": ctx["config"]["model"]["num_layers"],
                      **report}))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    sys.exit(main())
