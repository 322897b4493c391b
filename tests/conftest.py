"""Test configuration: force an 8-device virtual CPU mesh.

Must run before the first `import jax` anywhere in the test process, so the
env vars are set at conftest import time. Multi-chip sharding is validated on
this virtual mesh (no multi-chip TPU hardware in CI); the real chip is
exercised by chip_smoke.py instead.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Tests must not claim a chip on a TPU host.
jax.config.update("jax_platforms", "cpu")

# full-fp32 conv/matmul accumulation: parity tests compare against torch CPU
jax.config.update("jax_default_matmul_precision", "highest")

REFERENCE_ROOT = "/root/reference"
SAMPLE_VIDEO = os.path.join(REFERENCE_ROOT, "sample", "v_GGSY1Qvo990.mp4")


def _synthesize_sample(path: str) -> str:
    """A stand-in with the reference sample's nominal properties (355 frames,
    19.62 fps, 320x240) so the E2E/CLI tests run on hosts without the
    reference mount (e.g. external CI). Smooth moving gradients: natural-ish
    low-frequency content that codecs and the yuv420 paths handle like real
    video, not noise."""
    import cv2
    w = cv2.VideoWriter(path, cv2.VideoWriter_fourcc(*"mp4v"),
                        19.62, (320, 240))
    if not w.isOpened():  # degrade to the old skip, not a hard error
        pytest.skip("reference sample absent and cv2 cannot encode mp4v")
    yy, xx = np.mgrid[0:240, 0:320].astype(np.float32)
    for t in range(355):
        frame = np.stack([
            127 + 120 * np.sin(xx / 40 + t / 9),
            127 + 120 * np.sin(yy / 30 - t / 13),
            127 + 120 * np.sin((xx + yy) / 50 + t / 7),
        ], axis=-1)
        w.write(frame.clip(0, 255).astype(np.uint8))
    w.release()
    return path


#: committed copy of the synthesized stand-in (same nominal properties as
#: the reference sample), so the repo is test-self-contained without the
#: mount and without an encode-capable cv2 at test time
VENDORED_SAMPLE = os.path.join(os.path.dirname(__file__), "assets",
                               "v_synth_sample.mp4")


@pytest.fixture(scope="session")
def sample_video(tmp_path_factory):
    # VFT_FORCE_SYNTH_SAMPLE=1 exercises the synthesis path even when the
    # reference mount / vendored clip exists (validates the fallback itself)
    force = os.environ.get("VFT_FORCE_SYNTH_SAMPLE", "") not in ("", "0")
    if force:
        return _synthesize_sample(
            str(tmp_path_factory.mktemp("sample") / "v_synth_sample.mp4"))
    if os.path.exists(SAMPLE_VIDEO):
        return SAMPLE_VIDEO
    if os.path.exists(VENDORED_SAMPLE):
        return VENDORED_SAMPLE
    if os.environ.get("VFT_NO_SYNTH_SAMPLE"):
        pytest.skip("reference sample video not available")
    return _synthesize_sample(
        str(tmp_path_factory.mktemp("sample") / "v_synth_sample.mp4"))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
