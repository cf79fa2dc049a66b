"""Model families, and the one place that turns a config into a model.

A config dict (a TOML preset, a checkpoint's ``model_config``, a benchmark
file) names its family under ``family``; absent means ``"progen"``.
``build_model`` is what every CLI calls; ``decode_model`` and
``unstack_params`` are what the cached decoders and the serving engine ask
of whichever family they were handed.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen

__all__ = ["ProGen", "build_model", "decode_model", "require_progen",
           "unstack_params"]


def build_model(model_config: Mapping[str, Any], mesh=None):
    """The model a config dict describes; ``model.config`` is its parsed
    config. ``mesh`` is ProGen's (its explicit-collective attention path)."""
    family = model_config.get("family", "progen")
    if family == "progen":
        return ProGen(ProGenConfig.from_dict(model_config), mesh=mesh)
    if family != "latent_moe":
        raise ValueError(
            f"unknown model family {family!r} (known: progen, latent_moe)"
        )
    if mesh is not None:
        raise ValueError("the latent_moe family runs on one chip: no mesh")
    from progen_tpu.models.latent_moe import LatentMoE, LatentMoEConfig

    return LatentMoE(LatentMoEConfig.from_dict(model_config))


def require_progen(model, what: str):
    """``model`` if it is a ProGen; else the refusal of a path that was
    written for that family alone (the byte codec, the scorer, the
    trainer). Nothing falls back."""
    if not isinstance(model, ProGen):
        raise SystemExit(
            f"{what} runs the progen family only; a "
            f"{type(model).__name__} checkpoint is served by cli.serve "
            f"(ROADMAP.md says what else cannot run yet)"
        )
    return model


def decode_model(model, max_len: Optional[int] = None):
    """The decode-mode twin of ``model``. ``max_len`` bounds the state of
    a family whose cache grows with the sequence; ProGen's ring does not."""
    if isinstance(model, ProGen):
        from progen_tpu.models.progen import decode_model as twin

        return twin(model)
    from progen_tpu.models.latent_moe import decode_model as twin

    return twin(model, max_len)


def unstack_params(params, config):
    """``params`` in the layout the family's decode mode reads (ProGen's
    scanned layers unrolled; a family without stacked layers as it is)."""
    if isinstance(config, ProGenConfig):
        from progen_tpu.models.progen import unstack_params as unstack

        return unstack(params, config)
    return params
