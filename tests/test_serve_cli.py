"""The serve CLI's model selection and compile-cache helper."""
import pathlib

import jax
import pytest

from repro.launch import serve

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_execute_smoke_model_runs_the_fleet_path(capsys):
    """``--execute --smoke-model`` drives the executed fleet path end to end
    on the CPU stand-in (the full-width model is a chip workload)."""
    serve.main(["--streams", "2", "--frames", "2", "--network", "wifi",
                "--mobility", "static", "--execute", "--smoke-model",
                "--bucket-edges", "2", "--telemetry-sample", "0"])
    out = capsys.readouterr().out
    assert "[fleet aggregate] frames=4" in out
    assert "[fleet execute]" in out and "cloud_padded=1" in out


def test_smoke_model_requires_execute():
    with pytest.raises(SystemExit):
        serve.main(["--smoke-model"])


def test_smoke_model_keeps_the_paper_timing_plane():
    """``--smoke-model`` swaps only the executed model: the planner still
    prices the paper's ViT-L@384, and the weights are in the model dtype."""
    args = serve.build_parser().parse_args(["--execute", "--smoke-model"])
    profile, eng_cfg, cfg, params, images = serve.setup(args)
    assert (cfg.n_layers, cfg.d_model, cfg.dtype) == (4, 64, jax.numpy.float32)
    assert (profile.n_layers, profile.x0) == (24, 577)
    assert eng_cfg.execute and images.shape == (1, 64, 64, 3)
    assert all(a.dtype == cfg.dtype for a in jax.tree.leaves(params))


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env):
    """The CLI's cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it; nothing is overridden), else to the fixed ``<repo>/.jax_cache``.
    The config update is intercepted, so the test turns no cache on."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert serve.enable_compile_cache() == str(REPO / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert serve.enable_compile_cache() == env
        assert calls == []
