"""Builder for the hybrid conv / grouped-query-attention configurations with a
routed feed-forward (``model_type: lfm2_moe``): turns a configuration file
into the program's ``Lfm2StackedForCausalLM`` and hands the reference the same
arrays.  The file's ``model`` group holds the published ``config.json``'s keys
under their own names; ``layer_types`` stands whole at the file's top level
and the model takes its first ``num_hidden_layers`` entries."""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, Optional

REFERENCE = "benchmark.reference.lfm2_moe_ref"

#: how far under the reference's own k-th score (a sigmoid's, plus the bias)
#: a pick of the program's may lie and still be taken as the side of a tie
#: (PERF.md section 6, PR 33, has the readings it was set from)
ROUTE_MARGIN = 0.01

# the keys of the ``model`` group that are not fields of the program's config
_NOT_FIELDS = ("model_type", "rope_parameters")


def lfm2_config(config: Dict, num_layers: Optional[int] = None):
    from paddle_tpu.models.lfm2 import Lfm2Config

    fields = {k: v for k, v in config["model"].items() if k not in _NOT_FIELDS}
    # ``aot_compile.py --layers`` writes the depth under the GPT files' name
    num_layers = fields.pop("num_layers", num_layers)
    if num_layers is not None:
        fields["num_hidden_layers"] = int(num_layers)
    return Lfm2Config(rope_theta=float(config["model"]["rope_parameters"]["rope_theta"]),
                      layer_types=tuple(config["layer_types"]), **fields)


def model_config(config: Dict):
    """What a cell naming ``_ragged_kernel`` is compiled at: ``num_heads``,
    ``head_dim`` and ``num_layers`` are the geometry the KERNEL runs, not the
    model's 32 query heads of 64 in 10 layers: the K/V heads, the row of the
    K|V pool (a token's K and V side by side, 2 x 64: the queries are padded
    to it) and the layers that hold K/V (the attention layers), because
    ``test_ragged_kernel_compiles_at_the_cells_geometry`` shapes the pool and
    reckons its bytes from these three names (it builds a K and a V pool of
    that row, twice what the program holds).  ``config`` is the program's own
    config object; no weight is built."""
    cfg = lfm2_config(config)
    return SimpleNamespace(config=cfg, num_heads=cfg.num_key_value_heads,
                           head_dim=2 * cfg.head_dim,
                           num_layers=cfg.layer_types.count("full_attention"))


def build_model(config: Dict, *, seed: int, trainer: Optional[Dict] = None,
                num_layers: Optional[int] = None):
    """The model with bfloat16 weights from ``seed``, each drawn on the device
    in its storage dtype, the expert stacks a layer at a time."""
    import paddle_tpu as pt
    from paddle_tpu.models.lfm2 import Lfm2StackedForCausalLM

    pt.seed(int(seed) % (2 ** 31 - 1))
    model = Lfm2StackedForCausalLM(lfm2_config(config, num_layers))
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The program's own arrays in the reference's layout: one dict a layer
    (a period's layer is its slice of the scan's stack) and the expert stacks whole,
    each routed layer naming its first expert in them."""
    cfg = model.config
    lead, period, n_periods, trail = cfg.segments()

    def value(name):
        return getattr(model, name)._value

    experts = tuple(value(n) for n in ("moe_w1", "moe_w3", "moe_w2")) \
        if cfg.num_hidden_layers > cfg.num_dense_layers else None
    layers = []

    def segment(prefix, kinds, pick, routed):
        for j, kind in enumerate(kinds):
            names = (("conv_in", "conv_w", "conv_out") if kind == "conv" else
                     ("wq", "wk", "wv", "wo", "q_norm", "k_norm"))
            names += ("op_norm", "ffn_norm")
            names += ("router", "router_bias") if routed else ("w1", "w3", "w2")
            layer = {n: pick(value(f"{prefix}{j}_{n}")) for n in names}
            if routed:
                layer["experts"] = experts
                layer["expert_base"] = (len(layers) - cfg.num_dense_layers) * cfg.num_experts
            layers.append(layer)

    segment("lead", lead, lambda a: a, routed=False)
    for r in range(n_periods):
        segment("body", period, lambda a, r=r: a[r], routed=True)
    segment("trail", trail, lambda a: a, routed=True)
    return {"embed": value("embed"), "out_norm": value("out_norm"), "layers": layers}


def _routes_by_position(model):
    """The experts the program's newest cache logged for each position
    ``[routed layers, positions, k]`` (-1: nothing logged), or None unless the
    log holds ONE sequence: every position at most once."""
    import numpy as np

    log = model.recent_routes()
    if log is None or not len(log["positions"]):
        return None
    at = log["positions"]
    if len(np.unique(at)) != len(at):
        return None
    routes = np.full((log["experts"].shape[1], int(at.max()) + 1,
                      log["experts"].shape[2]), -1, np.int32)
    routes[:, at] = log["experts"].transpose(1, 0, 2)
    return routes


def reference_kwargs(model) -> Dict:
    """The model's sizes, and, where the program has just served ONE sequence
    (the runner's reference check), the experts it sent each position to:
    the reference takes them where they are a top-k of its own float32 scores
    to within ``ROUTE_MARGIN`` (``lfm2_moe_ref``'s docstring)."""
    cfg = model.config
    kw = {"layer_types": cfg.layer_types, "num_dense_layers": cfg.num_dense_layers,
          "heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
          "eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
          "top_k": cfg.num_experts_per_tok}
    routes = _routes_by_position(model)
    if routes is not None:
        kw.update(routes=routes, route_margin=ROUTE_MARGIN)
    return kw
