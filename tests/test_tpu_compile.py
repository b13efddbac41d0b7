"""Compile the served path's kernels and programs for a TPU v5e, without one.

The TPU compiler is installed next to JAX and compiles for a described
``v5e:2x2`` topology; nothing runs, so these tests say nothing about
results or speed.  They catch what the Pallas interpreter cannot see: block
layouts Mosaic refuses, programs that do not fit, kernels GSPMD cannot
partition.  Each asserts that the fused kernel is in the compiled program
(``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist every
worker imports this module.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import executor as exmod
from repro.core import engine, graph, perfmodel
from repro.core.pipeline import CompilerPipeline
from repro.kernels.bf16_conv.kernel import bf16_conv_gemm
from repro.kernels.bf16_conv.ops import conv2d_bf16_batch
from repro.kernels.int8_conv.kernel import int8_conv_gemm
from repro.kernels.int8_conv.ops import conv2d_int8_batch, fc_int8_batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # entries compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — any refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        if log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# ResNet-50 widths: a 512-channel 3x3 (K = 4608) and the 7x7/2 stem over
# 224x224 (N = 112 * 112 = 12544; M and K padded to the 128 block)
GEMM_SHAPES = [(512, 4608, 128), (128, 256, 12544)]


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
def test_int8_conv_gemm_compiles(one_chip, m, k, n):
    txt = _compiled_text(
        lambda w, c, b, s: int8_conv_gemm(w, c, b, s, relu=True),
        _spec((m, k), jnp.int8, one_chip), _spec((k, n), jnp.int8, one_chip),
        _spec((m,), jnp.int32, one_chip), _spec((m,), jnp.int32, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
def test_bf16_conv_gemm_compiles(one_chip, m, k, n):
    txt = _compiled_text(
        lambda w, c, b: bf16_conv_gemm(w, c, b, relu=True),
        _spec((m, k), jnp.bfloat16, one_chip),
        _spec((k, n), jnp.bfloat16, one_chip),
        _spec((m,), jnp.float32, one_chip))
    assert "tpu_custom_call" in txt


def test_conv2d_int8_batch_compiles(one_chip):
    # ResNet-50 layer-3 3x3: 256 channels over 14x14, bucket 8
    txt = _compiled_text(
        lambda x, w, b, s: conv2d_int8_batch(x, w, b, s, 3, 1, 1, relu=True),
        _spec((8, 256, 14, 14), jnp.int8, one_chip),
        _spec((256, 2304), jnp.int8, one_chip),
        _spec((256,), jnp.int32, one_chip), _spec((256,), jnp.int32, one_chip))
    assert "tpu_custom_call" in txt


def test_conv2d_bf16_batch_compiles(one_chip):
    txt = _compiled_text(
        lambda x, w, b: conv2d_bf16_batch(x, w, b, 3, 1, 1, relu=True),
        _spec((8, 256, 14, 14), jnp.bfloat16, one_chip),
        _spec((256, 2304), jnp.bfloat16, one_chip),
        _spec((256,), jnp.float32, one_chip))
    assert "tpu_custom_call" in txt


def test_fc_int8_batch_compiles(one_chip):
    # ResNet-50's classifier, bucket 8
    txt = _compiled_text(
        lambda x, w, b, s: fc_int8_batch(x, w, b, s),
        _spec((8, 2048), jnp.int8, one_chip),
        _spec((1000, 2048), jnp.int8, one_chip),
        _spec((1000,), jnp.int32, one_chip), _spec((1000,), jnp.int32, one_chip))
    assert "tpu_custom_call" in txt


@pytest.fixture(scope="module")
def lenet_art():
    return CompilerPipeline(graph.lenet5()).run()


@pytest.fixture
def chip_executor(lenet_art, monkeypatch):
    """LeNet-5 on the TPU plan: every CONV/FC the fused kernel, natively
    batched in batch programs, traced for the compiled kernel (this host's
    JAX sees a CPU and would pick the interpreter)."""
    monkeypatch.setattr(exmod, "_pallas_interpret", lambda: False)
    return exmod.BareMetalExecutor(
        lenet_art.trace, lenet_art.weight_image, lenet_art.cfg,
        kernel_plan=perfmodel.KERNEL_PALLAS, native_batch="force")


def _n_gemm(ex) -> int:
    return sum(d.unit in ("CONV", "FC") for d in ex.descs)


def test_lenet_single_image_program_compiles(chip_executor, one_chip):
    ex = chip_executor
    txt = ex._fn.lower(*ex._abstract_args(sharding=one_chip)) \
        .compile().as_text()
    assert txt.count("tpu_custom_call") >= _n_gemm(ex)


def test_lenet_bucket8_program_compiles(chip_executor, one_chip):
    ex = chip_executor
    txt = ex._make_batch_fn(8).lower(*ex._abstract_args(8, one_chip)) \
        .compile().as_text()
    assert txt.count("tpu_custom_call") >= _n_gemm(ex)


def test_lenet_bucket8_program_names_kernels_after_layers(chip_executor,
                                                          one_chip):
    """Every fused kernel in the compiled bucket-8 program is named after
    its descriptor, ``d<index>_<unit>``, and the program ``serve_b8``."""
    ex = chip_executor
    txt = ex._make_batch_fn(8).lower(*ex._abstract_args(8, one_chip)) \
        .compile().as_text()
    assert txt.startswith("HloModule jit_serve_b8,")
    calls = [line.split(" = ", 1)[0].strip() for line in txt.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= _n_gemm(ex)
    assert all(re.fullmatch(r"(ROOT )?%d\d\d_(conv|fc)(\.\d+)?", c)
               for c in calls), calls
    gemm = {f"d{i:02d}_{d.unit.lower()}" for i, d in enumerate(ex.descs)
            if d.unit in ("CONV", "FC")}
    assert {re.sub(r"^(ROOT )?%|\.\d+$", "", c) for c in calls} == gemm


def test_lenet_bucket8_lane_sharded_over_four_chips(chip_executor, topo):
    """The dispatcher's lane sharding: each chip runs the bucket program
    over its own two lanes, and the output stays split by lane."""
    ex = chip_executor
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("data",))
    lanes = NamedSharding(mesh, P("data"))
    params, x = ex._abstract_args(8, NamedSharding(mesh, P()))
    x = _spec(x.shape, x.dtype, lanes)
    compiled = ex._make_batch_fn(8, lanes).lower(params, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= _n_gemm(ex)
    out = compiled.output_shardings
    assert len(out.device_set) == 4 and not out.is_fully_replicated
