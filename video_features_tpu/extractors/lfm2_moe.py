"""LFM2-8B-A1B as a token-sequence extractor: the item, the windows, the
packed rows and the two outputs are ``extractors/token_sequence.py``'s; this
file names the model and adds the router's rule to the ``moe`` event (the
counts beside a feature do not say how the experts were chosen). The tied
head follows the last layer, which lives on the last pipeline stage, so
there is no ``show_pred``."""
from __future__ import annotations

from ..config import Config
from ..models import lfm2_moe as lfm
from .token_sequence import TokenSequenceExtractor


def _device_forward(arch: lfm.Arch, max_segments: int, dtype, params, rows):
    return lfm.segment_features(arch, max_segments, dtype, params, rows)


class ExtractLFM2Moe(TokenSequenceExtractor):
    model = lfm
    device_forward = staticmethod(_device_forward)
    default_stack_size = 16384

    def __init__(self, args: Config) -> None:
        super().__init__(args)
        self._moe_products.update(scoring="sigmoid", selection_bias=True,
                                  top_k=self.arch.num_experts_per_tok)
