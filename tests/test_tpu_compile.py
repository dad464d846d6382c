"""Compile the device path for a described TPU v5e, without a chip.

The main-path Pallas kernels at qwen2-7b / mixtral-8x7b widths must lower
through Mosaic (interpret mode on the CPU cannot show that), and
MiniEngine's prefill and decode steps at the depth ``chip_smoke.py`` serves
must leave at least 1.5 GB of the chip's memory free.

The topology is described inside a fixture: only one process may load the
TPU compiler library at a time, so nothing here touches it at import.
"""
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_gemm as _gg
from repro.models.common import AxisRules, shape_tree
from repro.models.model import build_model
from repro.serving.engine import decode_step, prefill_step

ROOT = Path(__file__).resolve().parent.parent
V5E_USABLE = 15.75 * 2 ** 30     # HBM XLA may allocate on one v5e chip
MIN_FREE = 1.5e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke_layers():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.LAYERS


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("kernel", ["flash", "decode", "grouped_gemm"])
def test_kernel_compiles_for_v5e(kernel, dtype, one_chip):
    def sds(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if kernel == "flash":        # qwen2-7b heads, S = T = 2048
        compiled = _compile(
            lambda q, k, v: _fa.flash_attention(q, k, v, causal=True),
            sds(1, 2048, 28, 128), sds(1, 2048, 4, 128),
            sds(1, 2048, 4, 128))
    elif kernel == "decode":     # 8 slots over a 4096-token cache
        compiled = _compile(_dec.decode_attention, sds(8, 28, 128),
                            sds(8, 4096, 4, 128), sds(8, 4096, 4, 128),
                            sds(8, dt=jnp.int32))
    else:                        # mixtral experts, 4096 -> 14336
        compiled = _compile(_gg.grouped_gemm, sds(8, 512, 4096),
                            sds(8, 4096, 14336), sds(8, dt=jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def _placed(tree, one_chip):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _compile_decode(model, cache, one_chip, slots):
    """MiniEngine's decode step with its slot cache donated, for v5e."""
    params = _placed(shape_tree(model.pds(), jnp.bfloat16), one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    return jax.jit(functools.partial(decode_step, model),
                   donate_argnums=1).lower(
        params, cache, i32((slots, 1)), i32((slots,))).compile()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_serving_step_fits_one_v5e(step, one_chip, smoke_layers):
    """qwen2-7b at its published widths, bf16, 8 slots x 2048: the weights,
    the slot cache and the step's own buffers leave >= 1.5 GB free."""
    cfg = get_config("qwen2-7b", layers=smoke_layers)
    model = build_model(cfg, AxisRules(None))
    cache = _placed(shape_tree(model.cache_pds(8, 2048), jnp.bfloat16),
                    one_chip)
    if step == "decode":
        compiled = _compile_decode(model, cache, one_chip, 8)
        resident = 0                 # the slot cache is an argument here
    else:                            # the longest prompt bucket
        params = _placed(shape_tree(model.pds(), jnp.bfloat16), one_chip)
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                                sharding=one_chip)
        compiled = jax.jit(functools.partial(prefill_step, model, 2048)
                           ).lower(params, i32((1, 1024)),
                                   i32((1,))).compile()
        resident = _nbytes(cache)    # the slot cache stays on the device
    m = compiled.memory_analysis()
    used = (resident + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert V5E_USABLE - used >= MIN_FREE, (step, used / 2 ** 30)


@pytest.mark.parametrize("arch, layers", [("qwen2-7b", None),
                                          ("mixtral-8x7b", 2)])
def test_decode_updates_the_slot_cache_in_place(arch, layers, one_chip,
                                                smoke_layers):
    """The decode step writes one K/V row per slot into the donated slot
    cache and copies nothing else of it: compiled for v5e at 8 slots x
    2048 (qwen2-7b at ``chip_smoke.LAYERS``, mixtral-8x7b at 2 layers, the
    fewest whose layers are scanned), its temporary buffers stay under a
    tenth of the cache's bytes.  Readings: 0.0011x qwen2-7b, 0.040x
    mixtral-8x7b (its MoE dispatch buffers); a step that rewrites the whole
    cache and returns it through the layer scan read 1.0x and 2.07x."""
    cfg = get_config(arch, layers=layers or smoke_layers)
    model = build_model(cfg, AxisRules(None))
    cache = _placed(shape_tree(model.cache_pds(8, 2048), jnp.bfloat16),
                    one_chip)
    m = _compile_decode(model, cache, one_chip, 8).memory_analysis()
    assert m.alias_size_in_bytes == _nbytes(cache)
    assert m.temp_size_in_bytes < 0.1 * _nbytes(cache), (
        arch, m.temp_size_in_bytes / _nbytes(cache))
