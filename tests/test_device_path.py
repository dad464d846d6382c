"""Guards of the device path that need no chip: kernel mode selection, the
hardware table, the compile-cache placement, the depth cut, and sweeps that
would put several processes on one chip."""
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.api import ModelRef, SimSpec, SpecError, run, sweep
from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.serve import hardware_for

ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None), ("rocm", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, want):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match=backend):
            ops._interpret()
    else:
        assert ops._interpret() is want


def test_hardware_for_maps_device_kind_and_rejects_unknown():
    assert hardware_for(SimpleNamespace(platform="tpu",
                                        device_kind="TPU v5 lite")) \
        is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9"):
        hardware_for(SimpleNamespace(platform="tpu", device_kind="TPU v9"))


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_env_var_alone(monkeypatch, tmp_path,
                                            cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_depth_cut_keeps_widths_and_is_bounded():
    full = get_config("qwen2-7b")
    cut = get_config("qwen2-7b", layers=24)
    assert cut.num_layers == 24
    assert (cut.d_model, cut.d_ff, cut.vocab_size, cut.num_heads) == \
        (full.d_model, full.d_ff, full.vocab_size, full.num_heads)
    for bad in (0, full.num_layers + 1):
        with pytest.raises(SpecError, match="model.layers"):
            SimSpec(model=ModelRef("qwen2-7b", layers=bad)).validate()
    # unset, the field serializes like specs that predate it
    assert "layers" not in SimSpec().to_dict()["model"]
    assert SimSpec(model=ModelRef(layers=2)).to_dict()["model"]["layers"] \
        == 2


def test_depth_cut_prices_fewer_layers():
    def tpot(layers):
        spec = SimSpec.from_dict({
            "model": {"name": "qwen2-7b", "layers": layers},
            "topology": {"hardware": "TPU-v5e"},
            "workload": {"n_requests": 4, "arrival": "burst",
                         "burst_size": 4, "prompt": "fixed",
                         "prompt_mean": 256, "output": "fixed",
                         "output_mean": 8}})
        return run(spec).summary["tpot_mean_s"]
    assert tpot(14) < tpot(None)


def test_sweep_refuses_jit_pricing_across_processes():
    base = SimSpec.from_dict({"model": {"name": "qwen2-7b", "smoke": True},
                              "opmodel": {"backend": "jit"},
                              "workload": {"n_requests": 2}})
    with pytest.raises(SpecError, match="jobs=1"):
        sweep(base, {"workload.rate": [1.0, 2.0]}, jobs=2)
