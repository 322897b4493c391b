"""The ``wav`` corpus kind: 16-bit mono PCM written with the standard
library's ``wave``. A frame is a sample and ``fps`` the sample rate."""
import wave

import numpy as np

SUFFIX = ".wav"
#: what makes a fixed file what it is: key -> default (None: no default)
GEOMETRY = {"fps": None}


def write(path, frames, spec, rng):
    """``frames`` samples: three seeded tones that drift in loudness, and
    noise, so that neighbouring examples differ."""
    t = np.arange(int(frames)) / float(spec["fps"])
    signal = 0.05 * rng.standard_normal(int(frames))
    for hz, beat in zip(rng.uniform(150.0, 3000.0, 3),
                        rng.uniform(0.3, 2.0, 3)):
        signal += 0.2 * np.sin(2 * np.pi * hz * t) * \
            (0.6 + 0.4 * np.sin(2 * np.pi * beat * t))
    pcm = np.clip(np.round(signal * 32767.0), -32768, 32767).astype("<i2")
    with wave.open(str(path), "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(int(spec["fps"]))
        w.writeframes(pcm.tobytes())
