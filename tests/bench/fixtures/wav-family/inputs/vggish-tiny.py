"""What a resident group of vggish-tiny holds: log-mel patches are natural
logarithms of mel energies, about -4.6 (silence, log 0.01) to 3; bytes 0..255
would be a spectrum no audio has."""
import numpy as np


def resident_batch(rng, shape, dtype):
    return np.clip(rng.normal(-1.5, 1.8, shape), -4.6, 4.0).astype(dtype)
