"""granite-4.0-h-small as a token-sequence extractor: the item, the windows,
the packed rows and the two outputs are ``extractors/token_sequence.py``'s;
this file names the model and, for ``show_pred``, reads the tied head."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..models import granite_hybrid as gh
from .token_sequence import (TOKEN_SUFFIX, WEIGHTS_SEED,  # noqa: F401
                             TokenSequenceExtractor, read_tokens, windows_of)


def _device_forward(arch: gh.Arch, max_segments: int, dtype, params, rows):
    return gh.segment_features(arch, max_segments, dtype, params, rows)


class ExtractGraniteHybrid(TokenSequenceExtractor):
    model = gh
    device_forward = staticmethod(_device_forward)
    default_stack_size = 4096

    def maybe_show_pred(self, ids: np.ndarray, windows) -> None:
        """The five likeliest next tokens after each window's last position,
        over the held rows of the vocabulary: computed outside the timed
        step, window by window, as ``ExtractR21D.maybe_show_pred`` runs its
        head on the host."""
        for start, end in windows:
            row = np.zeros((1, 2, self.stack_size), np.int32)
            row[0, 0, :end - start] = ids[start:end]
            row[0, 1, :end - start] = 1
            f, _ = gh.token_states(self.arch, self.runner.params,
                                   jnp.asarray(row), self.dtype)
            scores = np.asarray(gh.logits(
                self.arch, self.runner.params, f[0, end - start - 1]))
            best = np.argsort(scores)[::-1][:5]
            print(f"At tokens ({start}, {end})")
            for token in best:
                print(f"  {scores[token]:8.3f}  token {int(token)}")
