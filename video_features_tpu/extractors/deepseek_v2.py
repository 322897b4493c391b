"""DeepSeek-V2-Lite as a token-sequence extractor: the item, the windows,
the packed rows and the two outputs are ``extractors/token_sequence.py``'s;
this file names the model and states, once, how its attention runs. The
untied output head lives on the last pipeline stage, so there is no
``show_pred``."""
from __future__ import annotations

from typing import Dict

import numpy as np

from .. import telemetry
from ..models import deepseek_v2 as ds
from .token_sequence import TokenSequenceExtractor


def _device_forward(arch: ds.Arch, max_segments: int, dtype, params, rows):
    return ds.segment_features(arch, max_segments, dtype, params, rows)


class ExtractDeepSeekV2(TokenSequenceExtractor):
    model = ds
    device_forward = staticmethod(_device_forward)
    default_stack_size = 16384

    _mla_stated = False

    def extract(self, video_path: str) -> Dict[str, np.ndarray]:
        if not self._mla_stated:
            # feature values do not say which form of MLA ran: an ``mla``
            # event on the first item's span does (telemetry=true)
            self._mla_stated = True
            arch = self.arch
            telemetry.event(
                "mla", form="expanded", qk_head_dim=arch.qk_head_dim,
                v_head_dim=arch.v_head_dim, kv_lora_rank=arch.kv_lora_rank,
                rope="yarn", factor=arch.rope_factor,
                softmax_scale=ds.softmax_scale(arch))
        return super().extract(video_path)
