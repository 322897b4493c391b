"""feature_type -> extractor class dispatch (lazy imports).

Equivalent of the reference's if/elif ladder in main.py:21-38. Lazy importing
keeps startup fast and lets families with heavy optional deps fail only when
actually requested.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, List, Type


_DISPATCH = {
    "resnet": ("resnet", "ExtractResNet"),
    "r21d": ("r21d", "ExtractR21D"),
    "s3d": ("s3d", "ExtractS3D"),
    "i3d": ("i3d", "ExtractI3D"),
    "clip": ("clip", "ExtractCLIP"),
    "vggish": ("vggish", "ExtractVGGish"),
    "raft": ("raft", "ExtractRAFT"),
    "pwc": ("pwc", "ExtractPWC"),
    "granite_hybrid": ("granite_hybrid", "ExtractGraniteHybrid"),
    "deepseek_v2": ("deepseek_v2", "ExtractDeepSeekV2"),
    "lfm2_moe": ("lfm2_moe", "ExtractLFM2Moe"),
    "nemotron_h": ("nemotron_h", "ExtractNemotronH"),
}

#: families that consume the AUDIO track: in a multi-family run they
#: share one wav rip per video instead of subscribing to the FrameBus
AUDIO_FAMILIES = frozenset({"vggish"})


def parse_feature_types(feature_type: str) -> List[str]:
    """``'resnet,clip,s3d'`` -> ``['resnet', 'clip', 's3d']``.

    A single name passes through as a one-element list; every name must
    be registered and unique (duplicate families would race on the same
    output files)."""
    fams = [f.strip() for f in str(feature_type).split(",") if f.strip()]
    if not fams:
        raise NotImplementedError(f"Unknown feature_type: {feature_type!r}")
    seen = set()
    for f in fams:
        if f not in _DISPATCH:
            raise NotImplementedError(f"Unknown feature_type: {f!r}")
        if f in seen:
            raise ValueError(
                f"feature_type={feature_type!r}: family {f!r} is listed "
                "twice (its outputs would race on the same files)")
        seen.add(f)
    return fams


def get_extractor_cls(feature_type: str) -> Type:
    if feature_type not in _DISPATCH:
        raise NotImplementedError(f"Unknown feature_type: {feature_type}")
    module_name, cls_name = _DISPATCH[feature_type]
    import importlib
    full_module = f"{__package__}.extractors.{module_name}"
    try:
        module = importlib.import_module(full_module)
    except ModuleNotFoundError as e:
        if e.name != full_module:
            raise  # a real missing dependency, not an unimplemented family
        raise NotImplementedError(
            f"feature_type={feature_type!r} is registered but its extractor "
            "is not implemented yet") from e
    return getattr(module, cls_name)
