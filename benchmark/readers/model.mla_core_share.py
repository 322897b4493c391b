"""Share of the device's busy time in the traced sub-window spent under the
program's ``DeepSeekV2/attn/core`` scope: ``blockwise_attention``'s scan over
key blocks (scores, the streaming softmax's fold, mixing) in every layer."""
from vftbench import scopes


def read(m):
    return scopes.share(m, "DeepSeekV2/attn/core")
