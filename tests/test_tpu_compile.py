"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: each test lowers and compiles against a described `v5e:2x2`
topology (the TPU compiler ships with jaxlib) and checks that the kernel
survived as a Mosaic custom call. This is what interpret-mode tests cannot
show: block shapes, primitives and layouts the chip's compiler refuses.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_update as fu
from repro.kernels import mamba2_scan, ops, rwkv6_scan, sam_perturb

BUCKET = 64 * 1024 * 1024          # fp32 elements in one dtype bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _assert_kernel(text: str) -> None:
    assert "tpu_custom_call" in text


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# flash attention: forward, and value_and_grad through the reference VJP
# ---------------------------------------------------------------------------

ATTENTION = {
    # olmo-1b: 16 heads of 128, full causal, 2k context
    "olmo_1b": dict(q=(1, 2048, 16, 128), kv=(1, 2048, 16, 128), window=None),
    # GQA, head dim 64, sliding window
    "gqa_hd64_window": dict(q=(1, 2048, 8, 64), kv=(1, 2048, 2, 64),
                            window=1024),
    # gemma-2b: 8 heads of 256 over one kv head, 8k context
    "gemma_2b": dict(q=(1, 8192, 8, 256), kv=(1, 8192, 1, 256), window=None),
    # phi-3-vision: 32 heads of 96
    "phi3_hd96": dict(q=(1, 4096, 32, 96), kv=(1, 4096, 32, 96), window=None),
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("case", sorted(ATTENTION))
def test_flash_attention_compiles(one_chip, case, grad):
    c = ATTENTION[case]
    shapes = [_sds(one_chip, c["q"], jnp.bfloat16)] + \
        [_sds(one_chip, c["kv"], jnp.bfloat16)] * 2

    def attn(q, k, v):
        return ops.flash_attention(q, k, v, window=c["window"], impl="pallas")

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.value_and_grad(loss, argnums=(0, 1, 2)) if grad else attn
    _assert_kernel(_compiled_text(fn, *shapes))


def test_flash_attention_compiles_per_device_on_a_mesh(topo):
    """Under a 4-chip data-parallel mesh the kernel runs in a shard_map: XLA
    refuses to partition a Mosaic kernel by itself."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None, "model", None))
    shape = _sds(sh, (4, 1024, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, impl="pallas")
                       .astype(jnp.float32) ** 2)

    with jax.set_mesh(mesh):
        text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                              shape, shape, shape)
    _assert_kernel(text)


# ---------------------------------------------------------------------------
# sequence scans at published widths (zamba2-1.2b, rwkv6-7b heads)
# ---------------------------------------------------------------------------

def test_mamba2_scan_compiles(one_chip):
    b, s, h, p, g, n = 1, 2048, 64, 64, 1, 64
    shapes = [_sds(one_chip, (b, s, h, p), jnp.bfloat16),
              _sds(one_chip, (b, s, h)), _sds(one_chip, (h,)),
              _sds(one_chip, (b, s, g, n), jnp.bfloat16),
              _sds(one_chip, (b, s, g, n), jnp.bfloat16),
              _sds(one_chip, (h,))]

    def loss(*args):
        y, state = ops.mamba2_mix(*args, impl="pallas")
        return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(state)

    _assert_kernel(_compiled_text(
        lambda *a: mamba2_scan.mamba2_chunked(*a), *shapes))
    _assert_kernel(_compiled_text(
        jax.value_and_grad(loss, argnums=range(6)), *shapes))


def test_rwkv6_scan_compiles(one_chip):
    b, s, h, k = 1, 2048, 64, 64
    act = _sds(one_chip, (b, s, h, k), jnp.bfloat16)
    shapes = [act, act, act, _sds(one_chip, (b, s, h, k)),
              _sds(one_chip, (h, k))]

    def loss(*args):
        y, state = ops.rwkv6_mix(*args, impl="pallas")
        return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(state)

    _assert_kernel(_compiled_text(
        lambda *a: rwkv6_scan.rwkv6_chunked(*a), *shapes))
    _assert_kernel(_compiled_text(
        jax.value_and_grad(loss, argnums=range(5)), *shapes))


# ---------------------------------------------------------------------------
# weight-space kernels on one 64 Mi-element fp32 bucket
# ---------------------------------------------------------------------------

WEIGHT_SPACE = {
    "sq_norm": (1, lambda g: sam_perturb.sq_norm(g)),
    "sam_perturb": (2, lambda w, g: sam_perturb.sam_perturb(w, g, 0.05, 4.0)),
    "fused_axpy": (2, lambda x, y: fu.fused_axpy(0.5, x, y)),
    "fused_dot_norms": (2, fu.fused_dot_norms),
    "delta_amax": (3, fu.delta_amax),
    "delta_encode_i8": (3, lambda p, s, e: fu.delta_encode_i8(p, s, e, 0.01)),
    "sgd_epilogue": (3, lambda w, g, m: fu.sgd_epilogue(
        w, g, m, 0.7, 0.1, momentum=0.9, weight_decay=1e-4)),
    "adamw_epilogue": (4, lambda w, g, mu, nu: fu.adamw_epilogue(
        w, g, mu, nu, 0.7, 1e-3, 0.1, 0.001, weight_decay=0.01)),
}


@pytest.mark.parametrize("name", sorted(WEIGHT_SPACE))
def test_weight_space_kernel_compiles(one_chip, name):
    n_args, fn = WEIGHT_SPACE[name]
    _assert_kernel(_compiled_text(fn, *[_sds(one_chip, (BUCKET,))] * n_args))
