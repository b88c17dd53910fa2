"""Builder for the decoders of sliding-window and full grouped-query attention
layers with softmax-routed experts (``model_type: mellum``): turns a
configuration file into the program's ``Mellum2ForCausalLM`` and hands the
reference the same arrays.  The file's ``model`` group holds the published
``config.json``'s keys under their own names, ``layer_types`` and
``mlp_layer_types`` whole (the model takes their first
``num_hidden_layers`` entries)."""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, Optional

from .lfm2_builder import ROUTE_MARGIN, _routes_by_position

REFERENCE = "benchmark.reference.mellum2_ref"

# the keys of the ``model`` group that are not fields of the program's config
_NOT_FIELDS = ("num_layers",)


def mellum2_config(config: Dict, num_layers: Optional[int] = None):
    from paddle_tpu.models.mellum2 import Mellum2Config

    fields = {k: v for k, v in config["model"].items() if k not in _NOT_FIELDS}
    # ``aot_compile.py --layers`` writes the depth under the GPT files' name
    num_layers = config["model"].get("num_layers", num_layers)
    if num_layers is not None:
        fields["num_hidden_layers"] = int(num_layers)
    return Mellum2Config(**fields)


def model_config(config: Dict):
    """What a cell naming ``_ragged_kernel`` is compiled at: the 4 K/V heads
    of 128 and ``num_layers`` the full layers held (their pool has the cell's
    ``num_pages``; the window layers' ring is sized by the slots), because
    ``test_ragged_kernel_compiles_at_the_cells_geometry`` shapes the pool and
    reckons its bytes from these three names.  ``config`` is the program's
    own config object; no weight is built."""
    cfg = mellum2_config(config)
    return SimpleNamespace(config=cfg, num_heads=cfg.num_key_value_heads,
                           head_dim=cfg.head_dim,
                           num_layers=cfg.layer_types.count("full_attention"))


def build_model(config: Dict, *, seed: int, trainer: Optional[Dict] = None,
                num_layers: Optional[int] = None):
    """The model with bfloat16 weights from ``seed``, each drawn on the device
    in its storage dtype, the expert stacks eight experts at a time."""
    import paddle_tpu as pt
    from paddle_tpu.models.mellum2 import Mellum2ForCausalLM

    pt.seed(int(seed) % (2 ** 31 - 1))
    model = Mellum2ForCausalLM(mellum2_config(config, num_layers))
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The program's own arrays in the reference's layout: one dict a layer
    with its ``period`` (the reference slices the stacks as it casts them: no
    copy of a weight is made here) and the expert stacks whole, each layer
    naming its first expert in them."""
    cfg = model.config
    period = len(model._period)
    experts = tuple(getattr(model, n)._value for n in ("moe_w1", "moe_w3", "moe_w2"))
    layers = []
    for l in range(cfg.num_hidden_layers):
        j = l % period
        layer = {"period": l // period, "experts": experts,
                 "expert_base": l * cfg.num_experts}
        for name in model._names:
            if name.startswith(f"p{j}_"):
                layer[name[len(f"p{j}_"):]] = getattr(model, name)._value
        layers.append(layer)
    return {"embed": model.embed._value, "out_norm": model.out_norm._value,
            "lm_head": model.lm_head._value, "layers": layers}


def reference_kwargs(model) -> Dict:
    """The model's sizes, and, where the program has just served ONE sequence
    (the runner's reference check), the experts it sent each position to,
    taken where they are a top-k of the reference's own float32 softmax
    scores to within ``ROUTE_MARGIN``."""
    cfg = model.config
    kw = {"layer_types": cfg.layer_types, "heads": cfg.num_attention_heads,
          "kv_heads": cfg.num_key_value_heads, "eps": cfg.rms_norm_eps,
          "window": cfg.sliding_window, "rope_parameters": cfg.rope_parameters,
          "top_k": cfg.num_experts_per_tok}
    routes = _routes_by_position(model)
    if routes is not None:
        kw.update(routes=routes, route_margin=ROUTE_MARGIN)
    return kw
