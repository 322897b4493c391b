"""NVIDIA-Nemotron-3-Super-120B-A12B as a token-sequence extractor: the
item, the windows, the packed rows and the two outputs are
``extractors/token_sequence.py``'s; this file names the model and adds the
router's rule and the experts' form to the ``moe`` event (the counts beside
a feature do not say how the experts were chosen or what they compute). The
head follows the last layer, which lives on the last pipeline stage, so
there is no ``show_pred``."""
from __future__ import annotations

from ..config import Config
from ..models import nemotron_h as nem
from .token_sequence import TokenSequenceExtractor


def _device_forward(arch: nem.Arch, max_segments: int, dtype, params, rows):
    return nem.segment_features(arch, max_segments, dtype, params, rows)


class ExtractNemotronH(TokenSequenceExtractor):
    model = nem
    device_forward = staticmethod(_device_forward)
    default_stack_size = 16384

    def __init__(self, args: Config) -> None:
        super().__init__(args)
        arch = self.arch
        self._moe_products.update(
            scoring="sigmoid", selection_bias=True,
            top_k=arch.num_experts_per_tok, activation="relu2",
            latent=arch.moe_latent_size,
            scaling=arch.routed_scaling_factor,
            experts=f"{arch.experts_held} of {arch.n_routed_experts}")
