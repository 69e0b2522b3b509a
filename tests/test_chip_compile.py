"""The Pallas kernels compile for a TPU v5e at real widths.

Each test lowers and compiles one kernel ahead of time for one chip of a
*described* ``v5e:2x2`` topology: the TPU compiler (Mosaic) runs here,
with no chip attached, and refuses what the chip would refuse — shapes
it cannot lay out, operations it cannot lower, more VMEM than a kernel
may use.  The interpret-mode tests cannot see any of that.  One more
test compiles ``bsp_fft``'s cyclic layout, which is no kernel, to see
that it runs as strided slices on the chip rather than as a gather, and
one compiles the benchmark's Moonlight training step, which is no kernel
either, to see that it fits one chip's memory.

Widths are the ones ``chip_smoke.py`` runs: flash attention at
llama3.2-1b (H=32, Hkv=8, S=2048, D=64, bf16), ``fft_stage`` on
[2048, 2^15] and ``ssd_scan`` at mamba2-130m (H=24, P=64, N=128,
chunk 128, S=2048).

The topology is described inside module-scoped fixtures — never while a
module is imported — because only one process at a time may load the
TPU library; under pytest-xdist only the worker that runs this file
loads it.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.algorithms.fft import _strided_layout
from repro.kernels.fft_stage import kernel as fft_kernel
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd_scan.kernel import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without the chip; keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_forward_compiles(one_chip):
    q = _sds(one_chip, (1, 32, 2048, 64), jnp.bfloat16)
    kv = _sds(one_chip, (1, 8, 2048, 64), jnp.bfloat16)
    _compile(flash_attention, q, kv, kv)


def test_flash_attention_backward_compiles(one_chip):
    q = _sds(one_chip, (1, 32, 2048, 64), jnp.bfloat16)
    kv = _sds(one_chip, (1, 8, 2048, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward + the two backward passes (dK/dV and dQ)
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_fft_stage_compiles(one_chip):
    x = _sds(one_chip, (2048, 1 << 15))
    compiled = _compile(fft_kernel.fft_planes, x, x)
    # inputs, outputs and the one reshape copy fit one chip's HBM
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 16e9


def test_ssd_scan_compiles(one_chip):
    B, S, H, P, N = 1, 2048, 24, 64, 128
    _compile(functools.partial(ssd_scan, chunk=128),
             _sds(one_chip, (B, S, H, P)), _sds(one_chip, (B, S, H)),
             _sds(one_chip, (H,)), _sds(one_chip, (B, S, 1, N)),
             _sds(one_chip, (B, S, 1, N)))


def test_fft_cyclic_layout_compiles_without_gather(one_chip):
    """bsp_fft's cyclic layout at 2^28 points over 4 chips, on chip 0:
    strided slices, no gather, and no lane-padded [n/p, p] buffer."""
    x = _sds(one_chip, (1 << 28,), jnp.complex64)
    compiled = _strided_layout.lower(x, 4).compile()
    assert not re.search(r"\bgather\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes <= 3 * 2**30


def test_moonlight_train_step_fits_one_chip(topo):
    """The cell ``moonlight-16b-a3b-ep8.train8k``'s step at the published
    widths: one chip's share of an 8-way expert-parallel layer (the dense
    layer and 5 expert layers, 8 of 64 experts, 20,480 token ids), one
    sequence of 8,192 tokens, f32 parameters and AdamW moments.  Its
    arguments and temporaries fit in 15 GB of the chip's 16, the held
    experts run as grouped matmuls, and the layer loop casts each
    layer's weights inside it: no bfloat16 copy of the 5 expert layers'
    stacked expert weights."""
    from repro.configs import expert_share, get_config
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.runtime.train_step import build_train_step

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    cfg = expert_share(get_config("moonlight-16b-a3b", ep_degree=1),
                       layers=6, experts_held=8, vocab=20480)
    ts = build_train_step(cfg, mesh)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(lambda: adamw_init(params))

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                                  sharding=NamedSharding(mesh, PartitionSpec()))
    compiled = ts.step_fn.lower(
        placed(params, ts.param_sharding), placed(opt, ts.opt_sharding),
        {"tokens": tokens, "labels": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= 15e9
    text = compiled.as_text()
    assert "ragged-dot" in text
    assert not re.search(r"bf16\[5,8,2048,1408\]", text)
