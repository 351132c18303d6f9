"""Compile every registered kernel op for a TPU v5e that is described,
not attached: the TPU compiler refuses misaligned blocks, illegal
converts and VMEM overruns here, before any chip time is spent.

The topology is described inside a fixture, never while this module is
imported: only one process at a time may load the TPU library, and the
test workers each import every test file. Compiles go through the
``pallas`` backend at the real widths of ``repro.kernels.chip_cases``,
with the persistent compilation cache off (a compile for a described
chip is written to it but cannot be read back without one).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.kernels import chip_cases


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(name, args, kwargs, device):
    """``args``: shapes (``jax.ShapeDtypeStruct``), placed on ``device``."""
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=device)
             for a in args]
    op = kernels.get(name)
    with kernels.use_backend("pallas", clear_caches=False):
        return jax.jit(lambda *a: op(*a, **kwargs)).lower(*specs).compile()


@pytest.mark.parametrize("label", sorted(chip_cases.CASES))
def test_kernel_compiles_for_v5e(label, one_chip):
    case = chip_cases.CASES[label]
    out = {}

    def build(key):
        args, kwargs = case.build(key)
        out["kwargs"] = kwargs
        return args

    args = jax.eval_shape(build, jax.random.PRNGKey(0))
    compiled = _compile(case.op, args, out["kwargs"], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page", [8, 16, 32])
@pytest.mark.parametrize("storage", ["fp8", "bf16"])
@pytest.mark.parametrize("name", ["paged_gqa_decode", "paged_mla_decode"])
def test_paged_kernel_page_sizes(name, storage, page, one_chip):
    """Every candidate page size compiles without padding the pools: the
    argument bytes stay within 10% of their nominal size (the slack is
    the lane padding of the small scale / table / rope sidebands), so the
    engine keeps the smallest, 8."""
    build = (chip_cases.paged_gqa_args if name == "paged_gqa_decode"
             else chip_cases.paged_mla_args)
    out = {}

    def args_of(key):
        args, kwargs = build(key, page=page, storage=storage)
        out["kwargs"] = kwargs
        return args

    args = jax.eval_shape(args_of, jax.random.PRNGKey(0))
    compiled = _compile(name, args, out["kwargs"], one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    nominal = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                  for a in args)
    used = compiled.memory_analysis().argument_size_in_bytes
    assert used <= 1.10 * nominal, (used, nominal)


def test_paged_mla_kernel_carries_its_registry_name(one_chip):
    """The kernel's HLO instruction (and so its device-trace event) is
    named after its registry op, whatever its Python function is called."""
    out = {}

    def args_of(key):
        args, kwargs = chip_cases.paged_mla_args(key, page=64,
                                                 storage="fp8")
        out["kwargs"] = kwargs
        return args

    args = jax.eval_shape(args_of, jax.random.PRNGKey(0))
    text = _compile("paged_mla_decode", args, out["kwargs"],
                    one_chip).as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert [re.sub(r"\.\d+$", "", n) for n in names] == ["paged_mla_decode"]


# argument bytes of the full page walk (no live-page rule) at the batch
# cell's shape, compiled for the same described v5e
FULL_WALK_ARG_BYTES = 421_241_344


def test_paged_mla_kernel_at_the_batch_cell_shape(one_chip):
    """The live-page rule compiles at the shape the batch cell runs (128
    lanes, 80 pages of 64, fp8) as one kernel, and takes no argument
    bytes beyond the full walk's: ``last`` comes from ``qpos``, which the
    kernel already prefetches."""
    out = {}

    def args_of(key):
        args, kwargs = chip_cases.paged_mla_args(key, page=64, storage="fp8",
                                                 B=128, T=80 * 64)
        out["kwargs"] = kwargs
        return args

    args = jax.eval_shape(args_of, jax.random.PRNGKey(0))
    assert args[6].shape == (128, 80)
    compiled = _compile("paged_mla_decode", args, out["kwargs"], one_chip)
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", compiled.as_text())
    assert [re.sub(r"\.\d+$", "", n) for n in names] == ["paged_mla_decode"]
    used = compiled.memory_analysis().argument_size_in_bytes
    assert used <= FULL_WALK_ARG_BYTES, used
