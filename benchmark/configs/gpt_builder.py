"""Builder for the dense GPT configurations: turns a configuration file's
``model`` group into the program's ``GPTStackedForPretraining`` and hands the
reference the same weights.  A later architecture brings a builder module of
its own beside this one and names it in its configuration file."""
from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "benchmark.reference.gpt_ref"


def gpt_config(config: Dict, trainer: Optional[Dict] = None,
               num_layers: Optional[int] = None):
    from paddle_tpu.models import GPTConfig

    trainer = trainer or {}
    fields = dict(config["model"])
    if num_layers is not None:
        fields["num_layers"] = int(num_layers)
    return GPTConfig(hidden_dropout=0.0, attention_dropout=0.0,
                     use_flash_attention=bool(trainer.get("flash_attention", True)),
                     recompute_interval=int(trainer.get("recompute_interval", 0)),
                     **fields)


def build_model(config: Dict, *, seed: int, trainer: Optional[Dict] = None,
                num_layers: Optional[int] = None):
    """The model in the pure-bf16 regime (AMP O2), weights from ``seed``.
    The program initialises each stacked parameter on the device from its
    global generator, which ``seed`` seeds."""
    import paddle_tpu as pt
    from paddle_tpu.models import GPTStackedForPretraining

    pt.seed(int(seed) % (2 ** 31 - 1))
    model = GPTStackedForPretraining(gpt_config(config, trainer, num_layers))
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    return model


def reference_weights(model) -> Dict:
    """The system's own arrays in the reference's layout (no copy beyond the
    per-layer slices)."""
    from benchmark.reference.gpt_ref import LAYER_KEYS

    return {"embed": model.embeddings.word_embeddings.weight._value,
            "pos": model.embeddings.position_embeddings.weight._value,
            "ln_f_g": model.final_ln.weight._value,
            "ln_f_b": model.final_ln.bias._value,
            "layers": {k: getattr(model.decoder, k)._value for k in LAYER_KEYS}}


def reference_kwargs(model) -> Dict:
    return {"heads": model.config.num_heads, "eps": model.config.layer_norm_eps}
