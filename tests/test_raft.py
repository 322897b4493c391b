"""RAFT: parity against the actual reference torch model (imported read-only
from /root/reference as the numerical oracle)."""
import os
import sys

import numpy as np
from pathlib import Path
import pytest

torch = pytest.importorskip("torch")

import jax.numpy as jnp  # noqa: E402

from video_features_tpu.models import raft as raft_model  # noqa: E402

if "/root/reference" not in sys.path:
    sys.path.insert(0, "/root/reference")


def _ref_raft():
    try:
        from models.raft.raft_src.raft import RAFT as RefRAFT
    except Exception as e:  # pragma: no cover
        pytest.skip(f"reference RAFT not importable: {e}")
    torch.manual_seed(0)
    m = RefRAFT().eval()
    # give the cnet BNs non-trivial running stats so converter bugs show
    g = torch.Generator().manual_seed(1)
    for mod in m.modules():
        if isinstance(mod, torch.nn.BatchNorm2d):
            mod.running_mean.copy_(
                torch.rand(mod.running_mean.shape, generator=g) - 0.5)
            mod.running_var.copy_(
                torch.rand(mod.running_var.shape, generator=g) + 0.5)
    return m


def test_flax_matches_reference_torch():
    oracle = _ref_raft()
    params = raft_model.params_from_torch(oracle.state_dict())
    model = raft_model.RAFT(iters=20)

    # >=128 px per side: the reference's bilinear_sampler divides by
    # (W-1) per pyramid level, so a 1x1 level (inputs < 128) NaNs even in
    # torch; 128x160 -> levels 16x20, 8x10, 4x5, 2x2
    rng = np.random.default_rng(2)
    img1 = rng.uniform(0, 255, size=(1, 128, 160, 3)).astype(np.float32)
    img2 = rng.uniform(0, 255, size=(1, 128, 160, 3)).astype(np.float32)
    t1 = torch.from_numpy(img1).permute(0, 3, 1, 2)
    t2 = torch.from_numpy(img2).permute(0, 3, 1, 2)
    with torch.no_grad():
        want = oracle(t1, t2).permute(0, 2, 3, 1).numpy()  # (B, H, W, 2)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(img1),
                                 jnp.asarray(img2)))
    assert got.shape == want.shape == (1, 128, 160, 2)
    # 20 recurrent iterations amplify fp noise; flows here are O(1-10) px
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


def test_input_padder_pad_amounts():
    # pad to /8, sintel mode splits evenly (reference raft.py:30-40)
    x = np.zeros((1, 436, 1024, 3))
    (t, b), (l, r) = raft_model.pad_to_multiple(x)
    assert (t + b) == (440 - 436) and (l, r) == (0, 0)
    assert t == 2 and b == 2
    x = np.zeros((1, 48, 64, 3))
    assert raft_model.pad_to_multiple(x) == ((0, 0), (0, 0))


def test_corr_pyramid_and_lookup_match_torch():
    """Level shapes + the lookup itself vs the reference CorrBlock."""
    try:
        from models.raft.raft_src.corr import CorrBlock
    except ImportError:
        pytest.skip("reference RAFT source not available "
                    "(/root/reference mount absent on this host)")

    rng = np.random.default_rng(0)
    f1 = rng.standard_normal((1, 16, 20, 32)).astype(np.float32)
    f2 = rng.standard_normal((1, 16, 20, 32)).astype(np.float32)
    pyr = raft_model.build_corr_pyramid(jnp.asarray(f1), jnp.asarray(f2))
    assert [p.shape for p in pyr] == [
        (1, 320, 16, 20), (1, 320, 8, 10), (1, 320, 4, 5), (1, 320, 2, 2)]

    # fractional coords exercise the bilinear weights and border clipping
    gx, gy = np.meshgrid(np.arange(20.0), np.arange(16.0))
    coords = (np.stack([gx, gy], axis=-1)[None] +
              rng.uniform(-2, 2, size=(1, 16, 20, 2))).astype(np.float32)
    got = np.asarray(raft_model.corr_lookup_gather(pyr, jnp.asarray(coords)))

    t1 = torch.from_numpy(f1).permute(0, 3, 1, 2)
    t2 = torch.from_numpy(f2).permute(0, 3, 1, 2)
    blk = CorrBlock(t1, t2)
    tc = torch.from_numpy(coords).permute(0, 3, 1, 2)
    want = blk(tc).permute(0, 2, 3, 1).numpy()
    assert got.shape == want.shape == (1, 16, 20, 4 * 81)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_end_to_end_extraction(sample_video, tmp_path):
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.extractors.raft import ExtractRAFT

    cfg = load_config("raft", {
        "video_paths": sample_video, "device": "cpu",
        "batch_size": 4, "extraction_fps": 1, "side_size": 128,
        "on_extraction": "save_numpy", "allow_random_weights": True,
        "output_path": str(tmp_path / "out"), "tmp_path": str(tmp_path / "tmp"),
    })
    sanity_check(cfg)
    ex = ExtractRAFT(cfg)
    feats = ex._extract(sample_video)
    # ~18.1s @1fps = 19 frames -> 18 flow pairs; 240x320 -> min side 128
    # => 128x170, padded to /8 inside jit and unpadded back
    n, c, h, w = feats["raft"].shape
    assert (c, h, w) == (2, 128, 170) and n == len(feats["timestamps_ms"]) - 1
    assert (tmp_path / "out" / "raft" / f"{Path(sample_video).stem}_raft.npy").exists()


def test_flow_viz_matches_reference():
    import importlib.util
    if not os.path.exists("/root/reference/utils/flow_viz.py"):
        pytest.skip("reference flow_viz source not available "
                    "(/root/reference mount absent on this host)")
    spec = importlib.util.spec_from_file_location(
        "ref_flow_viz", "/root/reference/utils/flow_viz.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    from video_features_tpu.utils import flow_viz

    np.testing.assert_array_equal(flow_viz.make_colorwheel(),
                                  ref.make_colorwheel())
    rng = np.random.default_rng(3)
    flow = rng.uniform(-12, 12, size=(32, 40, 2)).astype(np.float32)
    np.testing.assert_array_equal(flow_viz.flow_to_image(flow),
                                  ref.flow_to_image(flow))


@pytest.mark.slow  # ~44s; test_io device-resize + the i3d sibling cover the fused path
def test_raft_device_resize_matches_host(sample_video, tmp_path, monkeypatch):
    """resize=device with side_size: the fused MXU resize in front of the
    flow net must match the host-PIL path closely (flow endpoint error well
    under a pixel for 2-LSB input deltas)."""
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check
    from video_features_tpu.registry import get_extractor_cls

    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "weights"))

    def feats(resize):
        args = load_config("raft", parse_dotlist([
            "feature_type=raft", "device=cpu", "batch_size=4",
            "extraction_fps=1", "side_size=128", "allow_random_weights=true",
            f"resize={resize}", f"output_path={tmp_path / 'o'}",
            f"tmp_path={tmp_path / 't'}", f"video_paths={sample_video}"]))
        sanity_check(args)
        return get_extractor_cls("raft")(args).extract(sample_video)

    host = feats("host")
    dev = feats("device")
    np.testing.assert_array_equal(host["timestamps_ms"],
                                  dev["timestamps_ms"])
    a, b = host["raft"], dev["raft"]  # (N, 2, H, W)
    assert a.shape == b.shape and a.shape[1] == 2
    err = np.abs(a - b)
    assert np.median(err) < 0.1 and np.percentile(err, 99) < 1.0, \
        (np.median(err), np.percentile(err, 99))


def test_iters_config_knob(tmp_path):
    """`iters` (raft) / `flow_iters` (i3d) expose the GRU refinement count
    the reference hardcodes at 20 (raft.py:118); default stays 20."""
    from video_features_tpu.config import load_config, sanity_check
    from video_features_tpu.registry import get_extractor_cls

    def build(**patch):
        args = load_config("raft", dict(
            {"feature_type": "raft", "device": "cpu", "batch_size": 1,
             "allow_random_weights": True, "video_paths": "x.mp4",
             "output_path": str(tmp_path / "o"),
             "tmp_path": str(tmp_path / "t")}, **patch))
        sanity_check(args)
        return get_extractor_cls("raft")(args)

    assert build().model.iters == 20
    assert build(iters=2).model.iters == 2


def _as_on_a_tpu(monkeypatch, proj_fits=True):
    """Force a kernel form on the CPU through the lookup's one decision
    function: it sees a TPU backend (and, for the per-level form, a stacked
    plane that fits no tile) while it decides, and nothing else does, so
    ``kernels.interpret_mode()`` still runs ``pallas_call`` in the
    interpreter."""
    import jax
    from video_features_tpu.kernels import corr_lookup as cl
    real = cl.prepare_lookup

    def prepare(pyramid):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            if not proj_fits:
                m.setattr(cl, "proj_lookup_supported", lambda p: False)
            return real(pyramid)

    monkeypatch.setattr(cl, "prepare_lookup", prepare)


def test_fused_convc1_path_matches_default(rng, monkeypatch):
    """The full model through ``proj`` (the fused lookup+convc1 scan path,
    the TPU's form — interpret mode here) and through the per-level form
    produces the same flow as through ``gather``: same param tree (the
    _Convc1Params twin shares nn.Conv's path/shapes), same numerics up to
    matmul reorder."""
    from video_features_tpu.models import raft as rm
    from video_features_tpu.telemetry.spans import VideoSpan

    params = rm.init_params(iters=4)
    assert params["update_block"]["encoder"]["convc1"]["kernel"].shape \
        == (1, 1, 324, 256)
    x1 = jnp.asarray(rng.integers(
        0, 255, size=(1, 64, 72, 3)).astype(np.float32))
    x2 = jnp.asarray(rng.integers(
        0, 255, size=(1, 64, 72, 3)).astype(np.float32))
    model = rm.RAFT(iters=4)

    def flow():
        with VideoSpan("v.mp4") as span:
            out = np.asarray(model.apply({"params": params}, x1, x2))
        (event,) = [e for e in span.record["events"]
                    if e["kind"] == "corr_lookup"]
        return out, event

    want, event = flow()
    assert (event["impl"], event["plane_cells"], event["plane_fill"]) \
        == ("gather", None, None)
    with monkeypatch.context() as m:
        _as_on_a_tpu(m)
        got, event = flow()
    # a 64 x 72 input: levels 8x9, 4x4, 2x2 and 1x1 on one 8 x 128 shelf
    assert (event["impl"], event["plane_cells"], event["plane_fill"]) \
        == ("proj", 1024, (72 + 16 + 4 + 1) / 1024)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)
    with monkeypatch.context() as m:
        _as_on_a_tpu(m, proj_fits=False)
        unfused, event = flow()
    assert (event["impl"], event["plane_cells"]) == ("level", None)
    np.testing.assert_allclose(unfused, want, atol=1e-3, rtol=1e-3)


def test_bfloat16_mode_close_to_f32(rng):
    """RAFT(dtype=bf16) + bf16 params: convs run MXU-native while pyramid/
    coords/norms stay f32 (models/raft.py RAFT docstring). Flow drift must
    stay well under the I3D flow stream's ToUInt8 quantization step."""
    import jax
    import jax.numpy as jnp
    from video_features_tpu.models import raft as rm
    from video_features_tpu.parallel.mesh import cast_floating

    params = rm.init_params(iters=4)
    x1 = jnp.asarray(rng.integers(0, 255, size=(1, 64, 72, 3)).astype(np.float32))
    x2 = jnp.asarray(rng.integers(0, 255, size=(1, 64, 72, 3)).astype(np.float32))
    f32 = np.asarray(jax.jit(lambda p, a, b: rm.RAFT(iters=4).apply(
        {"params": p}, a, b))(params, x1, x2))
    bf16 = np.asarray(jax.jit(lambda p, a, b: rm.RAFT(
        iters=4, dtype=jnp.bfloat16).apply({"params": p}, a, b))(
        cast_floating(params, jnp.bfloat16), x1, x2))
    d = np.abs(bf16 - f32)
    assert np.isfinite(bf16).all()
    # loose bound: random weights amplify bf16 noise vs trained ones
    assert np.median(d) < 0.1 and np.percentile(d, 99) < 1.0, \
        (np.median(d), np.percentile(d, 99))


def test_precision_bfloat16_wires_model_dtype(tmp_path, monkeypatch):
    """precision=bfloat16 must reach RAFT.dtype (and f32 stay default) —
    wiring only, no forward (bf16 CPU compiles are minutes-slow)."""
    import jax.numpy as jnp
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check
    from video_features_tpu.registry import get_extractor_cls
    monkeypatch.setenv("VFT_WEIGHTS_DIR", str(tmp_path / "w"))
    for precision, want in (("float32", jnp.float32),
                            ("bfloat16", jnp.bfloat16)):
        args = load_config("raft", parse_dotlist([
            "feature_type=raft", "device=cpu", f"precision={precision}",
            "allow_random_weights=true", f"output_path={tmp_path / 'o'}",
            f"tmp_path={tmp_path / 't'}", "video_paths=x.mp4"]))
        sanity_check(args)
        ex = get_extractor_cls("raft")(args)
        assert ex.model.dtype == want, precision


def test_corr_lookup_states_what_ran(rng, monkeypatch, capsys):
    """Feature values are the same on every lookup form, so the form is
    stated: a ``corr_lookup`` span event per traced forward (impl /
    compiled-or-interpreted / fallback), and a printed line whenever a size
    gate replaced ``proj``."""
    from video_features_tpu.kernels import corr_lookup as cl
    from video_features_tpu.telemetry.spans import VideoSpan
    params = raft_model.init_params(iters=1)
    x = jnp.asarray(rng.integers(0, 255, size=(1, 32, 32, 3))
                    .astype(np.float32))
    model = raft_model.RAFT(iters=1)

    def stated():
        with VideoSpan("v.mp4") as span:
            flow = np.asarray(model.apply({"params": params}, x, x))
        (event,) = [e for e in span.record["events"]
                    if e["kind"] == "corr_lookup"]
        return flow, {k: event[k] for k in ("impl", "compiled", "fallback")}

    want, event = stated()
    assert event == {"impl": "gather", "compiled": None, "fallback": None}
    assert "corr lookup" not in capsys.readouterr().out

    # off-TPU pallas_call is the interpreter, and the statement says so
    with monkeypatch.context() as m:
        _as_on_a_tpu(m)
        _, event = stated()
    assert event == {"impl": "proj", "compiled": False, "fallback": None}
    assert "corr lookup" not in capsys.readouterr().out

    # both VMEM size gates: the one-hot twin runs, and says that it did
    with monkeypatch.context() as m:
        _as_on_a_tpu(m, proj_fits=False)
        m.setattr(cl, "pallas_lookup_supported", lambda p: False)
        got, event = stated()
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)
    assert event["impl"] == "onehot" and event["compiled"] is None
    assert "4x4 level-0 plane fits no legal VMEM tile" in event["fallback"]
    assert "corr lookup: impl=onehot in place of proj: a 4x4 level-0 " \
        "plane fits no legal VMEM tile" in capsys.readouterr().out


def test_lookup_environment_variables_are_inert(monkeypatch):
    """The environment selects no lookup form: the variables an older build
    read at trace time change neither the decision nor the traced
    program."""
    import jax
    from video_features_tpu.kernels import corr_lookup as cl
    model = raft_model.RAFT(iters=1)
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    params = jax.eval_shape(lambda: raft_model.init_params(1))
    pyramid = tuple(jnp.zeros((1, 16, 4 >> i, 4 >> i)) for i in range(4))

    def traced():
        return (str(jax.make_jaxpr(lambda p: model.apply(
            {"params": p}, x, x))(params)), cl.prepare_lookup(pyramid)[1])

    for name in ("VFT_CORR_LOOKUP", "VFT_FUSE_CONVC1"):
        monkeypatch.delenv(name, raising=False)
    before = traced()
    monkeypatch.setenv("VFT_CORR_LOOKUP", "onehot")
    monkeypatch.setenv("VFT_FUSE_CONVC1", "0")
    assert traced() == before
    assert before[1] == cl.LookupForm("gather")
