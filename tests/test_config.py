"""Config system: YAML defaults, dotlist overrides, sanity_check semantics."""
import os

from pathlib import Path

import pytest

from video_features_tpu.config import (Config, load_config, merge,
                                       parse_dotlist, sanity_check)

pytestmark = pytest.mark.quick


def test_dotlist_parsing_types():
    cfg = parse_dotlist([
        "feature_type=resnet", "batch_size=16", "extraction_fps=null",
        "video_paths=[a.mp4,b.mp4]", "show_pred=true", "a.b=1",
    ])
    assert cfg.feature_type == "resnet"
    assert cfg.batch_size == 16
    assert cfg.extraction_fps is None
    assert cfg.video_paths == ["a.mp4", "b.mp4"]
    assert cfg.show_pred is True
    assert cfg.a.b == 1


def test_yaml_defaults_merged_under_cli():
    cfg = load_config("resnet", parse_dotlist(["batch_size=32"]))
    assert cfg.batch_size == 32            # CLI wins
    assert cfg.model_name == "resnet50"    # YAML default survives


def test_all_families_have_configs():
    for ft in ("i3d", "r21d", "s3d", "vggish", "resnet", "raft", "pwc", "clip"):
        cfg = load_config(ft)
        assert cfg.feature_type == ft
        assert "output_path" in cfg and "tmp_path" in cfg


def test_sanity_check_namespaces_output_paths(tmp_path):
    cfg = load_config("resnet", {
        "video_paths": "x.mp4", "device": "cpu",
        "output_path": str(tmp_path / "out"), "tmp_path": str(tmp_path / "tmp"),
    })
    sanity_check(cfg)
    # feature_type/model_name appended (reference utils/utils.py:112-125)
    assert cfg.output_path.endswith(os.path.join("out", "resnet", "resnet50"))
    assert cfg.tmp_path.endswith(os.path.join("tmp", "resnet", "resnet50"))


def test_sanity_check_slash_in_model_name(tmp_path):
    cfg = load_config("clip", {
        "video_paths": "x.mp4", "device": "cpu",
        "output_path": str(tmp_path / "out"), "tmp_path": str(tmp_path / "tmp"),
    })
    sanity_check(cfg)
    assert cfg.output_path.endswith(os.path.join("clip", "ViT-B_32"))


def test_sanity_check_rejects_duplicate_stems(tmp_path):
    cfg = load_config("resnet", {
        "video_paths": ["a/v.mp4", "b/v.mp4"], "device": "cpu",
        "output_path": str(tmp_path / "o"), "tmp_path": str(tmp_path / "t"),
    })
    with pytest.raises(AssertionError):
        sanity_check(cfg)


def test_sanity_check_fps_total_exclusive(tmp_path):
    cfg = load_config("resnet", {
        "video_paths": "x.mp4", "device": "cpu", "extraction_fps": 5,
        "extraction_total": 10,
        "output_path": str(tmp_path / "o"), "tmp_path": str(tmp_path / "t"),
    })
    with pytest.raises(AssertionError):
        sanity_check(cfg)


def test_sanity_check_i3d_stack_size(tmp_path):
    cfg = load_config("i3d", {
        "video_paths": "x.mp4", "device": "cpu", "stack_size": 5,
        "output_path": str(tmp_path / "o"), "tmp_path": str(tmp_path / "t"),
    })
    with pytest.raises(AssertionError):
        sanity_check(cfg)


def test_merge_deep():
    a = Config({"x": {"y": 1, "z": 2}, "k": 0})
    b = Config({"x": {"y": 5}})
    m = merge(a, b)
    assert m.x.y == 5 and m.x.z == 2 and m.k == 0


def test_compilation_cache_knob(monkeypatch, tmp_path):
    """compilation_cache_dir (cli.py _enable_compilation_cache): null/empty
    disables; unset environment -> 'auto' is the fixed in-checkout
    directory; where JAX_COMPILATION_CACHE_DIR is set nothing points the
    cache anywhere else, whatever the key says."""
    from video_features_tpu import compile_cache
    from video_features_tpu.cli import _enable_compilation_cache

    calls = {}
    import jax
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("VFT_COMPILE_CACHE_DIR", raising=False)

    _enable_compilation_cache(dict(compilation_cache_dir=None))
    _enable_compilation_cache(dict(compilation_cache_dir=False))  # yaml 'false'
    # XLA:CPU executables are microarch-scoped: 'auto' never persists them
    _enable_compilation_cache(dict(compilation_cache_dir="auto",
                                   device="cpu"))
    assert not calls
    repo = Path(__file__).resolve().parent.parent
    _enable_compilation_cache(dict(compilation_cache_dir="auto"))
    assert calls["jax_compilation_cache_dir"] == str(repo / ".cache" / "xla") \
        == compile_cache.default_root()
    _enable_compilation_cache(dict(compilation_cache_dir=str(tmp_path / "x")))
    assert calls["jax_compilation_cache_dir"] == str(tmp_path / "x")

    calls.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    _enable_compilation_cache(dict(compilation_cache_dir="auto"))
    _enable_compilation_cache(dict(compilation_cache_dir=str(tmp_path / "x")))
    assert "jax_compilation_cache_dir" not in calls
    # small programs still get cached there (and counted as misses)
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_device_tpu_is_verified_and_auto_announces_cpu(tmp_path, capsys):
    """No path that hides the device: an explicit device=tpu on a host
    without one raises at extractor init instead of extracting on the
    CPU, and device=auto says so when it resolves to the CPU."""
    from video_features_tpu.config import resolve_device
    from video_features_tpu.extractors.base import BaseExtractor

    assert resolve_device("tpu") == "tpu"  # verified where it is used
    with pytest.raises(RuntimeError, match=r"device=tpu.*'cpu'.*devices"):
        BaseExtractor(Config({
            "feature_type": "resnet", "device": "tpu",
            "output_path": str(tmp_path / "o"),
            "tmp_path": str(tmp_path / "t")}))
    capsys.readouterr()
    assert resolve_device("auto") == "cpu"
    assert "found no TPU" in capsys.readouterr().out


def test_video_workers_auto(tmp_path):
    """video_workers=auto resolves to a bounded thread count in the CLI and
    is forced to 1 under print/show_pred by sanity_check."""
    from video_features_tpu.config import load_config, parse_dotlist, \
        sanity_check

    args = load_config("resnet", parse_dotlist(
        ["feature_type=resnet", "video_workers=auto",
         "video_paths=/root/reference/sample/v_GGSY1Qvo990.mp4"]))
    sanity_check(args)  # on_extraction defaults to print
    assert args.video_workers == 1
    args2 = load_config("resnet", parse_dotlist(
        ["feature_type=resnet", "video_workers=auto",
         "on_extraction=save_numpy", f"output_path={tmp_path / 'o'}",
         f"tmp_path={tmp_path / 't'}",
         "video_paths=/root/reference/sample/v_GGSY1Qvo990.mp4"]))
    sanity_check(args2)
    assert args2.video_workers == "auto"  # resolved at run time in cli.main


REF_CONFIGS = "/root/reference/configs"


@pytest.mark.skipif(not os.path.isdir(REF_CONFIGS),
                    reason="reference configs not mounted")
def test_config_defaults_match_reference():
    """Drop-in compat contract: every key in the reference's per-family
    config exists here with the SAME default (so a plain
    `feature_type=<fam>` run means the same thing in both frameworks).
    Sole exemption: `device` — the reference defaults to 'cuda:0', which
    this framework accepts and maps to 'auto' (config.py:resolve_device)."""
    import yaml

    from video_features_tpu.config import build_cfg_path

    for fam in ("resnet", "r21d", "s3d", "i3d", "clip",
                "vggish", "raft", "pwc"):
        with open(os.path.join(REF_CONFIGS, f"{fam}.yml")) as f:
            ref = yaml.safe_load(f)
        with open(build_cfg_path(fam)) as f:
            ours = yaml.safe_load(f)
        for key, want in ref.items():
            assert key in ours, f"{fam}: reference key {key!r} missing"
            if key == "device":
                continue
            assert ours[key] == want, (
                f"{fam}.{key}: default {ours[key]!r} diverges from the "
                f"reference's {want!r} — a drop-in user would silently get "
                "different behavior")


def test_resize_key_validation(tmp_path):
    base = dict(video_paths="a.mp4", output_path=str(tmp_path / "o"),
                tmp_path=str(tmp_path / "t"))
    for ok in ("auto", "host", "device", None):
        cfg = load_config("resnet", {**base, "resize": ok})
        sanity_check(cfg)  # must not raise
    cfg = load_config("resnet", {**base, "resize": "gpu"})
    with pytest.raises(ValueError):
        sanity_check(cfg)


def test_flow_configs_carry_no_lookup_key_and_every_key_is_classified():
    """RAFT's lookup form is the code's choice (kernels/corr_lookup.py
    prepare_lookup): the two keys that once selected it are in no config,
    and every key the flow-bearing configs do carry is still classified for
    the feature cache's fingerprint (vft-lint VFT001)."""
    from video_features_tpu import cache
    for family in ("raft", "i3d"):
        cfg = load_config(family)
        assert not {"corr_lookup_impl", "fuse_convc1"} & set(cfg)
        assert not (cache.SEMANTIC_KEYS & cache.NON_SEMANTIC_KEYS)
        assert not set(cfg) - cache.SEMANTIC_KEYS - cache.NON_SEMANTIC_KEYS


def test_history_alerts_key_validation(tmp_path):
    """history=/alerts= (ISSUE 13, telemetry/history.py +
    telemetry/alerts.py): booleans validated at launch, and both
    require telemetry=true — samples and rule evaluation ride the
    heartbeat cadence, so enabling them without a recorder would
    silently watch nothing."""
    base = dict(video_paths="a.mp4", output_path=str(tmp_path / "o"),
                tmp_path=str(tmp_path / "t"))
    cfg = load_config("resnet", {**base, "telemetry": True,
                                 "history": True, "alerts": True})
    sanity_check(cfg)  # must not raise
    for bad in ({"history": "yes"}, {"alerts": "on"}):
        with pytest.raises(ValueError):
            sanity_check(load_config("resnet", {**base,
                                                "telemetry": True, **bad}))
    for flag in ("history", "alerts"):
        with pytest.raises(ValueError, match="telemetry=true"):
            sanity_check(load_config("resnet", {**base, flag: True}))


def test_fleet_key_validation(tmp_path):
    """fleet= scheduling keys (parallel/queue.py): a typo'd mode or a
    queue run missing its prerequisites must fail at launch, before N
    hosts start claiming (ISSUE 8)."""
    base = dict(video_paths="a.mp4", output_path=str(tmp_path / "o"),
                tmp_path=str(tmp_path / "t"))
    sanity_check(load_config("resnet", {**base, "fleet": "static"}))
    # queue mode needs telemetry (lease renewal) + a file sink
    sanity_check(load_config("resnet", {
        **base, "fleet": "queue", "telemetry": True,
        "on_extraction": "save_numpy"}))
    with pytest.raises(ValueError, match="fleet="):
        sanity_check(load_config("resnet", {**base, "fleet": "dynamic"}))
    with pytest.raises(ValueError, match="telemetry"):
        sanity_check(load_config("resnet", {
            **base, "fleet": "queue", "on_extraction": "save_numpy"}))
    with pytest.raises(ValueError, match="file sink"):
        sanity_check(load_config("resnet", {
            **base, "fleet": "queue", "telemetry": True}))
    with pytest.raises(ValueError, match="fleet_lease_s"):
        sanity_check(load_config("resnet", {**base, "fleet_lease_s": 0}))
    with pytest.raises(ValueError, match="fleet_max_reclaims"):
        sanity_check(load_config("resnet",
                                 {**base, "fleet_max_reclaims": 0}))
    with pytest.raises(ValueError, match="fleet_canary"):
        sanity_check(load_config("resnet",
                                 {**base, "fleet_canary": "yes"}))


def test_serve_slo_key_validation(tmp_path):
    """serve_slo_s (serve.py SLO objective, ISSUE 10): null disables,
    a positive float passes, zero/negative/garbage fail at launch —
    never silently count zero violations against a broken objective."""
    base = dict(video_paths="a.mp4", output_path=str(tmp_path / "o"),
                tmp_path=str(tmp_path / "t"))
    cfg = load_config("resnet", base)
    assert cfg.serve_slo_s is None  # shipped default: disabled
    sanity_check(cfg)
    sanity_check(load_config("resnet", {**base, "serve_slo_s": 2.5}))
    for bad in (0, -1.0, "fast"):
        with pytest.raises(ValueError, match="serve_slo_s"):
            sanity_check(load_config("resnet",
                                     {**base, "serve_slo_s": bad}))


def test_compile_cache_key_validation(tmp_path):
    """compile_cache= / compile_cache_dir= (compile_cache.py, ISSUE 11):
    'auto'/true/false pass, anything else fails at launch — a typo'd
    switch must not silently compile cold forever."""
    base = dict(video_paths="a.mp4", output_path=str(tmp_path / "o"),
                tmp_path=str(tmp_path / "t"))
    sanity_check(load_config("resnet", {**base, "compile_cache": True}))
    sanity_check(load_config("resnet", {**base, "compile_cache": False}))
    sanity_check(load_config("resnet", {
        **base, "compile_cache": "auto",
        "compile_cache_dir": str(tmp_path / "cc")}))
    with pytest.raises(ValueError, match="compile_cache="):
        sanity_check(load_config("resnet",
                                 {**base, "compile_cache": "always"}))
    with pytest.raises(ValueError, match="compile_cache_dir"):
        sanity_check(load_config("resnet",
                                 {**base, "compile_cache_dir": 7}))
