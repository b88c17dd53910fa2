"""Builder for the decoder-hybrid-decoder configurations (``model_type:
phi4flash``): turns a configuration file into the program's
``Phi4FlashForCausalLM`` and hands the reference the same arrays.  The file's
``model`` group holds the published ``config.json``'s keys under their own
names; the state-space sizes the ``config.json`` leaves to its config class's
defaults stand in the file's ``state_space`` group (and under ``assumed``, with
the reason)."""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, Optional

REFERENCE = "benchmark.reference.phi4flash_ref"

# the keys of the ``model`` group that are not fields of the program's config
_NOT_FIELDS = ("model_type", "num_layers")


def flash_config(config: Dict, num_layers: Optional[int] = None):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig

    fields = {k: v for k, v in config["model"].items() if k not in _NOT_FIELDS}
    # ``aot_compile.py --layers`` writes the depth under the GPT files' name
    num_layers = config["model"].get("num_layers", num_layers)
    if num_layers is not None:
        fields["num_hidden_layers"] = int(num_layers)
    return Phi4FlashConfig(**fields, **config["state_space"])


def model_config(config: Dict):
    """What a cell naming ``_ragged_kernel`` is compiled at: ``num_heads``,
    ``head_dim`` and ``num_layers`` are the geometry the KERNEL runs, not the
    model's 40 query heads of 64 in 32 layers: a pool head is a PAIR of K/V
    heads (10 of them), its row the pair's two heads side by side (2 x 64;
    the queries are padded to it), and ``num_layers`` is 1, the one layer
    whose pool has the cell's ``num_pages`` (the full-attention layer; the
    window layers' rings and the state rows are sized by the slots), because
    ``test_ragged_kernel_compiles_at_the_cells_geometry`` shapes the pool and
    reckons its bytes from these three names.  ``config`` is the program's own
    config object; no weight is built."""
    cfg = flash_config(config)
    return SimpleNamespace(config=cfg, num_heads=cfg.num_key_value_heads // 2,
                           head_dim=2 * cfg.head_dim, num_layers=1)


def build_model(config: Dict, *, seed: int, trainer: Optional[Dict] = None,
                num_layers: Optional[int] = None):
    """The model with bfloat16 weights from ``seed``, each drawn on the device
    in its storage dtype."""
    import paddle_tpu as pt
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM

    pt.seed(int(seed) % (2 ** 31 - 1))
    model = Phi4FlashForCausalLM(flash_config(config, num_layers))
    model.eval()
    return model


def reference_weights(model) -> Dict:
    """The program's own arrays in the reference's layout: one dict a layer
    with its ``kind``.  A layer of a period hands the scan's stacks whole with
    ``period``, its index in them: the reference takes its slice as it casts
    it, so no copy of a weight is made here (32 layers' slices beside the
    engine's pools do not fit the chip)."""
    cfg = model.config

    def layer(seg, kind, period=None):
        out = {"kind": kind} if period is None else {"kind": kind, "period": period}
        for name in model._names:
            if name.startswith(seg + "_"):
                out[name[len(seg) + 1:]] = getattr(model, name)._value
        return out

    layers = []
    for i in range(cfg.self_periods):
        layers += [layer("self0", "ssm", i), layer("self1", "attn", i)]
    layers += [layer("mid0", "ssm"), layer("mid1", "attn")]
    for i in range(cfg.cross_periods):
        layers += [layer("cross0", "gmu", i), layer("cross1", "cross", i)]
    assert len(layers) == cfg.num_hidden_layers
    return {"embed": model.embed._value, "out_g": model.out_g._value,
            "out_b": model.out_b._value, "layers": layers}


def reference_kwargs(model) -> Dict:
    """The model's sizes: no layer makes a pick that a higher precision could
    make otherwise, so nothing of a run is handed over."""
    cfg = model.config
    return {"heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
            "window": cfg.sliding_window, "eps": cfg.layer_norm_eps,
            "d_state": cfg.mamba_d_state, "dt_rank": cfg.mamba_dt_rank}
