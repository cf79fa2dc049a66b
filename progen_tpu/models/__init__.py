"""Model families, and the one place that turns a config into a model.

A config dict (a TOML preset, a checkpoint's ``model_config``, a benchmark
file) names its family under ``family``; absent means ``"progen"``.
``build_model`` is what every CLI calls; ``decode_model`` and
``unstack_params`` are what the cached decoders and the serving engine ask
of whichever family they were handed. ``FAMILIES`` is the one table they
all read.
"""

from __future__ import annotations

import importlib
from typing import Any, Mapping, Optional

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen

__all__ = ["FAMILIES", "ProGen", "build_model", "decode_model",
           "require_progen", "unstack_params"]

# family -> (module, config class, model class); the module's
# ``decode_model`` is the family's decode twin. ProGen's is imported
# above; the others load when a config first names them.
FAMILIES = {
    "progen": ("progen_tpu.models.progen", "ProGenConfig", "ProGen"),
    "latent_moe": ("progen_tpu.models.latent_moe", "LatentMoEConfig",
                   "LatentMoE"),
    "linear_sparse": ("progen_tpu.models.linear_sparse",
                      "LinearSparseConfig", "LinearSparse"),
    "window_moe": ("progen_tpu.models.window_moe", "WindowMoEConfig",
                   "WindowMoE"),
}


def _family_of(model) -> str:
    name = type(model).__name__
    return next(f for f, (_, _, cls) in FAMILIES.items() if cls == name)


def build_model(model_config: Mapping[str, Any], mesh=None):
    """The model a config dict describes; ``model.config`` is its parsed
    config. ``mesh`` is ProGen's (its explicit-collective attention path)."""
    family = model_config.get("family", "progen")
    if family not in FAMILIES:
        raise ValueError(
            f"unknown model family {family!r} (known: {', '.join(FAMILIES)})"
        )
    if family == "progen":
        return ProGen(ProGenConfig.from_dict(model_config), mesh=mesh)
    if mesh is not None:
        raise ValueError(f"the {family} family runs on one chip: no mesh")
    module, config_cls, model_cls = FAMILIES[family]
    module = importlib.import_module(module)
    return getattr(module, model_cls)(
        getattr(module, config_cls).from_dict(model_config)
    )


def require_progen(model, what: str):
    """``model`` if it is a ProGen; else the refusal of a path that was
    written for that family alone (the byte codec, the scorer, the
    trainer). Nothing falls back."""
    if not isinstance(model, ProGen):
        others = ", ".join(f for f in FAMILIES if f != "progen")
        raise SystemExit(
            f"{what} runs the progen family only; a "
            f"{type(model).__name__} checkpoint is served by cli.serve, as "
            f"every other family is ({others}; ROADMAP.md says what else "
            f"cannot run yet)"
        )
    return model


def decode_model(model, max_len: Optional[int] = None):
    """The decode-mode twin of ``model``. ``max_len`` bounds the state of
    a family whose cache grows with the sequence; ProGen's ring does not."""
    twin = importlib.import_module(FAMILIES[_family_of(model)][0]).decode_model
    return twin(model) if isinstance(model, ProGen) else twin(model, max_len)


def unstack_params(params, config):
    """``params`` in the layout the family's decode mode reads (ProGen's
    scanned layers unrolled; a family without stacked layers as it is)."""
    if isinstance(config, ProGenConfig):
        from progen_tpu.models.progen import unstack_params as unstack

        return unstack(params, config)
    return params
