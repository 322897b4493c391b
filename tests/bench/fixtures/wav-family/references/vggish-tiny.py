"""VGGish in plain ``jax.numpy`` and float32, after the AudioSet release:
the log-mel front end (25 ms periodic-Hann windows every 10 ms, 512-point
FFT magnitudes, 64 HTK-mel triangles from 125 to 7,500 Hz, log(x + 0.01),
patches of 96 frames every 96), six 3x3 convolutions with ReLU and four 2x2
max pools, three dense layers with ReLU. No flax, no kernel, no batching.

Of the program it imports the loader alone (``models/vggish.py
init_params``: the seeded float32 weights before the extractor rounds them),
and computes from those. The tree it is handed, the one the window ran in
bfloat16, it only holds against them: leaf for leaf the loader's weights
rounded once, so a fault in the program's preparation of its weights stops
the check and is not shared by both sides. ``control`` is the same
arithmetic with weights and every layer's input rounded to float8 (e4m3),
the nearest precision under the configuration's bfloat16: put in the
program's place it has to fail ``checks/vggish-tiny.py compare()``."""
import wave

import jax
import jax.numpy as jnp
import numpy as np

CONVS = ("features_0", "features_3", "features_6", "features_8",
         "features_11", "features_13")
POOL_AFTER = ("features_0", "features_3", "features_8", "features_13")
DENSE = ("embeddings_0", "embeddings_2", "embeddings_4")


def mel(hertz):
    return 1127.0 * np.log(1.0 + np.asarray(hertz) / 700.0)


def log_mel_patches(path):
    with wave.open(str(path), "rb") as w:
        assert (w.getsampwidth(), w.getframerate()) == (2, 16000), path
        pcm = np.frombuffer(w.readframes(w.getnframes()), "<i2")
        samples = pcm.reshape(-1, w.getnchannels()).mean(axis=1) / 32768.0
    starts = np.arange(0, len(samples) - 400 + 1, 160)
    frames = samples[starts[:, None] + np.arange(400)]
    hann = 0.5 - 0.5 * np.cos(2 * np.pi * np.arange(400) / 400)
    magnitude = np.abs(np.fft.rfft(frames * hann, 512))         # (T, 257)
    bins, edges = mel(np.linspace(0.0, 8000.0, 257)), \
        np.linspace(mel(125.0), mel(7500.0), 66)
    lower, centre, upper = edges[:-2], edges[1:-1], edges[2:]
    weights = np.maximum(0.0, np.minimum(
        (bins[:, None] - lower) / (centre - lower),
        (upper - bins[:, None]) / (upper - centre)))            # (257, 64)
    weights[0] = 0.0
    log_mel = np.log(magnitude @ weights + 0.01)
    n = (len(log_mel) - 96) // 96 + 1
    return np.stack([log_mel[96 * i:96 * i + 96] for i in range(n)]
                    ).astype(np.float32)[..., None]             # (N, 96, 64, 1)


def conv3x3(x, kernel, bias):
    """'Same' cross-correlation as nine shifted matrix products."""
    h, w = x.shape[1:3]
    padded = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return bias + sum(padded[:, dy:dy + h, dx:dx + w] @ kernel[dy, dx]
                      for dy in range(3) for dx in range(3))


def unrounded_weights(params):
    """The loader's float32 tree, once the timed tree is seen to be it."""
    from video_features_tpu.models.vggish import init_params
    loaded = init_params()
    timed = jax.tree_util.tree_leaves_with_path(params)
    whole = jax.tree_util.tree_leaves_with_path(loaded)
    assert [p for p, _ in timed] == [p for p, _ in whole], "another tree"
    for (path, ran), (_, full) in zip(timed, whole):
        assert np.array_equal(np.asarray(ran), np.asarray(
            jnp.asarray(full).astype(ran.dtype))), \
            f"{jax.tree_util.keystr(path)}: not the loader's, rounded once"
    return loaded


def forward(params, x, rounded=lambda a: a):
    for name in CONVS:
        x = jax.nn.relu(conv3x3(rounded(x), rounded(params[name]["kernel"]),
                                params[name]["bias"]))
        if name in POOL_AFTER:
            b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    x = x.reshape(x.shape[0], -1)
    for name in DENSE:
        x = jax.nn.relu(rounded(x) @ rounded(params[name]["kernel"])
                        + params[name]["bias"])
    return np.asarray(x)


def features(params, config, check_path):
    x = jnp.asarray(log_mel_patches(check_path))
    return {config["run_keys"]["feature_type"]:
            forward(unrounded_weights(params), x)}


def control(params, config, check_path):
    x = jnp.asarray(log_mel_patches(check_path))
    return {config["run_keys"]["feature_type"]: forward(
        unrounded_weights(params), x,
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32))}
