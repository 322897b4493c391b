"""I3D: parity against the actual reference torch model (imported read-only
from /root/reference as the numerical oracle) + E2E rgb extraction."""
import importlib.util
import os

import numpy as np
from pathlib import Path
import pytest

torch = pytest.importorskip("torch")

import jax.numpy as jnp  # noqa: E402

from video_features_tpu.models import i3d as i3d_model  # noqa: E402
from tests.torch_oracles import randomize_bn_stats  # noqa: E402

REF_I3D = "/root/reference/models/i3d/i3d_src/i3d_net.py"


def _load_reference_i3d():
    if not os.path.exists(REF_I3D):
        pytest.skip("reference I3D source not available")
    spec = importlib.util.spec_from_file_location("ref_i3d", REF_I3D)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("modality,in_ch", [("rgb", 3), ("flow", 2)])
def test_flax_matches_reference_torch(modality, in_ch):
    ref = _load_reference_i3d()
    torch.manual_seed(0)
    oracle = ref.I3D(num_classes=400, modality=modality).eval()
    randomize_bn_stats(oracle)
    params = i3d_model.params_from_torch(oracle.state_dict())
    model = i3d_model.I3D(num_classes=400)

    # T=18 exercises ceil_mode in BOTH strided 3D maxpools (T: 18 -> 9 ->
    # ceil -> 5 -> ceil -> 3) — the floor-mode result would be a different
    # shape, so a pooling bug cannot hide
    x = np.random.default_rng(1).uniform(
        low=-1, high=1, size=(1, 18, 224, 224, in_ch)).astype(np.float32)
    xt = torch.from_numpy(x).permute(0, 4, 1, 2, 3)
    with torch.no_grad():
        want_feats = oracle(xt, features=True).numpy()
        want_softmax, want_logits = oracle(xt, features=False)
        want_logits = want_logits.numpy()
    got_feats = np.asarray(model.apply({"params": params}, jnp.asarray(x),
                                       features=True))
    got_logits = np.asarray(model.apply({"params": params}, jnp.asarray(x),
                                        features=False))
    assert got_feats.shape == want_feats.shape == (1, 1024)
    np.testing.assert_allclose(got_feats, want_feats, atol=5e-4, rtol=5e-4)
    assert got_logits.shape == want_logits.shape == (1, 400)
    np.testing.assert_allclose(got_logits, want_logits, atol=5e-4, rtol=5e-4)


def test_tf_same_pads_match_reference_formula():
    ref = _load_reference_i3d()
    for kernel, stride in [((7, 7, 7), (2, 2, 2)), ((3, 3, 3), (1, 1, 1)),
                           ((1, 3, 3), (1, 2, 2)), ((2, 2, 2), (2, 2, 2)),
                           ((3, 3, 3), (2, 2, 2)), ((1, 1, 1), (1, 1, 1))]:
        # reference returns (Hlo,Hhi,Wlo,Whi,Tlo,Thi) for ConstantPad3d
        # (last-dim-first); ours is ((Tlo,Thi),(Hlo,Hhi),(Wlo,Whi))
        hlo, hhi, wlo, whi, tlo, thi = ref.get_padding_shape(kernel, stride)
        assert i3d_model.tf_same_pads(kernel, stride) == \
            ((tlo, thi), (hlo, hhi), (wlo, whi))


def test_end_to_end_rgb_extraction(sample_video, tmp_path):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.extractors.i3d import ExtractI3D

    cfg = load_config("i3d", {
        "video_paths": sample_video, "device": "cpu", "streams": "rgb",
        "stack_size": 16, "step_size": 16, "extraction_fps": 6,
        "clip_batch_size": 2,
        "on_extraction": "save_numpy", "allow_random_weights": True,
        "output_path": str(tmp_path / "out"), "tmp_path": str(tmp_path / "tmp"),
    })
    sanity_check(cfg)
    ex = ExtractI3D(cfg)
    feats = ex._extract(sample_video)
    # ~18.1s @6fps = ~109 frames; stacks need 17 frames, step 16 ->
    # stacks complete at frames 17, 33, ..., 97 -> 6 stacks
    assert feats["rgb"].shape == (6, 1024)
    assert feats["timestamps_ms"].shape == (6,)
    assert ex.output_feat_keys == ["rgb", "fps", "timestamps_ms"]


def test_flow_quantize_chain_matches_reference_transforms():
    """The jitted RAFT-side transform tail (crop of the padded field, clamp,
    ToUInt8) + the I3D-side ScaleTo1_1 vs the reference torch Compose
    (extract_i3d.py:53-59). Uses a synthetic flow field so only the transform
    semantics (floor-rule crop, round-half-to-even float quantization) are
    under test — RAFT itself has its own parity test."""
    import importlib.util

    if not os.path.exists("/root/reference/models/transforms.py"):
        pytest.skip("reference transforms source not available")
    spec = importlib.util.spec_from_file_location(
        "ref_transforms", "/root/reference/models/transforms.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    rng = np.random.default_rng(7)
    # flow values straddling the clamp boundary, incl. exact +/-20 -> the
    # 255.5 -> 256 round-half-even edge, at an odd padded size (261x349) so
    # the center-crop floor rule is exercised
    flow = rng.uniform(-25, 25, size=(3, 2, 261, 349)).astype(np.float32)
    flow[0, 0, 0, 0] = 20.0
    flow[0, 1, 0, 1] = -20.0

    want = ref.TensorCenterCrop(224)(torch.from_numpy(flow))
    want = ref.Clamp(-20, 20)(want)
    want = ref.ToUInt8()(want)
    want = ref.ScaleTo1_1()(want).numpy()

    # ours: NHWC; crop+clamp+quantize as in _raft_quantized_flow, scale as
    # in _i3d_flow_forward
    x = jnp.asarray(flow.transpose(0, 2, 3, 1))
    hp, wp = x.shape[1], x.shape[2]
    i, j = (hp - 224) // 2, (wp - 224) // 2
    q = jnp.round(128.0 + 255.0 / 40.0 * jnp.clip(x[:, i:i + 224, j:j + 224],
                                                  -20.0, 20.0))
    got = np.asarray(q * (2.0 / 255.0) - 1.0).transpose(0, 3, 1, 2)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.slow  # ~50s; the rgb-only and flow-only E2Es below stay quick
def test_end_to_end_two_stream_extraction(sample_video, tmp_path):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.extractors.i3d import ExtractI3D

    cfg = load_config("i3d", {
        "video_paths": sample_video, "device": "cpu",
        "stack_size": 10, "step_size": 10, "extraction_fps": 1,
        "clip_batch_size": 1,
        "on_extraction": "save_numpy", "allow_random_weights": True,
        "output_path": str(tmp_path / "out"), "tmp_path": str(tmp_path / "tmp"),
    })
    sanity_check(cfg)
    ex = ExtractI3D(cfg)
    feats = ex._extract(sample_video)
    # ~18.1s @1fps = 19 frames; a stack needs 11 frames, step 10 -> one
    # stack completes at frame 11 (next would need frame 21 > 19)
    assert ex.output_feat_keys == ["rgb", "flow", "fps", "timestamps_ms"]
    assert feats["rgb"].shape == (1, 1024)
    assert feats["flow"].shape == (1, 1024)
    assert feats["timestamps_ms"].shape == (1,)
    out_dir = tmp_path / "out" / "i3d"
    assert (out_dir / f"{Path(sample_video).stem}_rgb.npy").exists()
    assert (out_dir / f"{Path(sample_video).stem}_flow.npy").exists()


def test_end_to_end_flow_pwc_extraction(sample_video, tmp_path):
    """The flow_type=pwc composition path (extract_i3d.py:154-155: no
    padder, crop on the unpadded input-resolution field)."""
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.extractors.i3d import ExtractI3D

    cfg = load_config("i3d", {
        "video_paths": sample_video, "device": "cpu", "streams": "flow",
        "flow_type": "pwc",
        "stack_size": 10, "step_size": 10, "extraction_fps": 1,
        "clip_batch_size": 1,
        "on_extraction": "save_numpy", "allow_random_weights": True,
        "output_path": str(tmp_path / "out"), "tmp_path": str(tmp_path / "tmp"),
    })
    sanity_check(cfg)
    ex = ExtractI3D(cfg)
    feats = ex._extract(sample_video)
    assert ex.output_feat_keys == ["flow", "fps", "timestamps_ms"]
    assert feats["flow"].shape == (1, 1024)
    assert (tmp_path / "out" / "i3d" / f"{Path(sample_video).stem}_flow.npy").exists()


@pytest.mark.slow  # ~140s: the slowest quick-tier test by 3x; raft/io device-resize siblings keep the fused-resize path in the quick tier
def test_i3d_device_resize_matches_host(sample_video, tmp_path, monkeypatch):
    """resize=device (both streams: resize fused into rgb-I3D and the
    RAFT pair chain) must match the host-PIL path within the 2-LSB input
    quantization difference."""
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check
    from video_features_tpu.registry import get_extractor_cls

    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "weights"))

    def feats(resize):
        args = load_config("i3d", parse_dotlist([
            "feature_type=i3d", "device=cpu", "stack_size=10",
            "step_size=10", "extraction_fps=2", "allow_random_weights=true",
            f"resize={resize}", f"output_path={tmp_path / 'o'}",
            f"tmp_path={tmp_path / 't'}", f"video_paths={sample_video}"]))
        sanity_check(args)
        return get_extractor_cls("i3d")(args).extract(sample_video)

    host = feats("host")
    dev = feats("device")
    np.testing.assert_array_equal(host["timestamps_ms"],
                                  dev["timestamps_ms"])
    for stream in ("rgb", "flow"):
        a, b = host[stream], dev[stream]
        assert a.shape == b.shape and a.shape[1] == 1024
        cos = np.sum(a * b, axis=1) / (np.linalg.norm(a, axis=1)
                                       * np.linalg.norm(b, axis=1) + 1e-9)
        assert np.all(cos > 0.99), (stream, cos.min())


def test_device_flow_multi_stack_chunking(rng):
    """_device_flow fuses k stacks' pair batches into one flow forward
    (round-4 throughput lever); the chunk/reshape/slice algebra must hand
    each stack exactly its own pairs, padded runner rows dropped."""
    from video_features_tpu.extractors.i3d_flow import FlowStream

    class FakeRunner:
        def dispatch(self, pairs):
            # per-pair signature + 3 fake padded rows (dispatch() keeps
            # padding, the caller must slice it off)
            x = jnp.asarray(pairs, jnp.float32)
            return jnp.pad(x.mean(axis=(1, 2, 3, 4)), (0, 3))

    fs = FlowStream.__new__(FlowStream)
    fs.pair_runner = FakeRunner()
    group = rng.integers(0, 255, size=(3, 5, 16, 16, 3)).astype(np.uint8)
    fs.stack_batch = 2  # chunks of 2 + ragged 1
    got = np.asarray(fs._device_flow(group))
    fs.stack_batch = 1  # the round-3 per-stack path
    want = np.asarray(fs._device_flow(group))
    assert got.shape == want.shape == (3, 4)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    pairs0 = np.stack([group[0, :-1], group[0, 1:]], axis=1)
    np.testing.assert_allclose(
        got[0], pairs0.reshape(4, -1).mean(axis=1), rtol=1e-5)


def test_stacks_per_forward_geometry_budget():
    """Auto flow-stack batching: 4 at the 224px flagship geometry, scaled
    down for larger sources so the correlation pyramid fits HBM."""
    import jax
    from video_features_tpu.extractors.i3d_flow import (
        _flow_pyramid_budget, _stacks_per_forward)
    budget = _flow_pyramid_budget(jax.devices()[0])
    assert budget == 7 * 1024 ** 3  # the CPU backend reports no HBM
    assert _stacks_per_forward(64, 224, 224, budget) == 4
    assert _stacks_per_forward(64, 256, 454, budget) == 2  # 1.9 GB/stack
    assert _stacks_per_forward(64, 436, 1024, budget) == 1  # 20 GB/stack
    assert _stacks_per_forward(16, 64, 64, budget) == 4    # cap wins


def test_flow_pyramid_budget_reads_the_device():
    """On a TPU the budget is a share of what the device reports, and a
    device that reports nothing is an error, not an assumed 16 GB chip."""
    from video_features_tpu.extractors.i3d_flow import _flow_pyramid_budget

    class FakeTpu:
        platform = "tpu"

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    assert _flow_pyramid_budget(FakeTpu({"bytes_limit": 32 << 30})) \
        == 14 << 30
    with pytest.raises(RuntimeError, match="bytes_limit"):
        _flow_pyramid_budget(FakeTpu(None))
