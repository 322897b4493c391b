"""Test configuration: force an 8-device virtual CPU mesh.

Must run before the first `import jax` anywhere in the test process, so the
env vars are set at conftest import time. Multi-chip sharding is validated on
this virtual mesh (no multi-chip TPU hardware in CI); the real chip is
exercised by chip_smoke.py instead.
"""
import faulthandler
import os
import signal

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

#: seconds one test may take: thirteen times the slowest alone at PR 34
#: (22 s), two and a half times the slowest beside the driver's five other
#: workers (84-124 s in six whole runs). Past it the test fails with every
#: thread's stack on stderr and the run goes on
TEST_TIME_LIMIT_S = 300

_stderr = None


def pytest_configure(config):
    # fd 2 as it is before the per-test capture swaps it: the stacks of a
    # test that is cut must reach the terminal, not a capture file that
    # dies with the process
    global _stderr
    _stderr = os.fdopen(os.dup(2), "w")


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    # xdist 3.8 under --dist loadfile puts a dead worker's file back in the
    # queue with the test it died in still to run, so one crash repeats
    # until the run gives up (--max-worker-restart): count the test as
    # run (xdist reports it failed) and let the rest of its file go on
    unit = getattr(sched, "workqueue", {}).get(crashitem.rsplit("::", 1)[0])
    if unit is not None:
        unit[crashitem] = True


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Every test's own clock (this machine has no pytest-timeout)."""
    limit = TEST_TIME_LIMIT_S

    def expired(signum, frame):
        faulthandler.cancel_dump_traceback_later()
        print(f"\nTIME LIMIT: {request.node.nodeid} outlasted {limit}s; "
              "every thread's stack:", file=_stderr, flush=True)
        faulthandler.dump_traceback(file=_stderr, all_threads=True)
        pytest.fail(f"outlasted the {limit}s time limit (stacks on stderr)",
                    pytrace=False)

    # a main thread stuck inside a C call (cv2's read() under a released
    # capture was one) never runs `expired`: a tenth later faulthandler's
    # own thread dumps the stacks and ends the process, and xdist reports
    # the test as crashed and replaces the worker
    faulthandler.dump_traceback_later(limit * 1.1, exit=True, file=_stderr)
    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


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
