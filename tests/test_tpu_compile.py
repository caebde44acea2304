"""Compile-only checks: the Pallas kernels compile for a TPU v5e.

Nothing runs: each kernel is lowered at the widths of a model the repo
serves or trains and compiled for a described (not attached) v5e chip.
Interpret mode accepts block shapes and DMA slices the TPU's compiler
refuses; these tests catch that without a chip.  Each asserts that the
compiled program holds the kernel (``tpu_custom_call``).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import moe as moe_k
from repro.kernels import paged_attention as paged_k
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.nn.moe import moe_capacity

DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.usefixtures("no_compile_cache")
class TestKernelsCompileForV5e:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_paged_decode_llama32_1b(self, one_chip, dtype):
        """KV=8, G=4, hd=64, page 16 (llama3.2-1b)."""
        B, KV, G, hd, ps, P = 8, 8, 4, 64, 16, 16
        N = 1 + B * P
        s = lambda sh, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
            sh, dt, sharding=one_chip)
        hlo = _compile(paged_k.paged_decode_pallas, s((B, KV, G, hd)),
                       s((N, ps, KV, hd)), s((N, ps, KV, hd)),
                       s((B, P), jnp.int32), s((B,), jnp.int32))
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_moe_dispatch_olmoe(self, one_chip, dtype):
        """D=2048, E=64, K=8 (olmoe-1b-7b), 256-token groups."""
        G, S, D, E, K = 2, 256, 2048, 64, 8
        C = moe_capacity(S, E, K)
        s = lambda sh, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
            sh, dt, sharding=one_chip)
        hlo = _compile(
            lambda x, src, w: moe_k.dispatch_pallas(
                x, src, w, num_experts=E, capacity=C),
            s((G, S, D)), s((G, E, C), jnp.int32), s((G, E, C), jnp.float32))
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_moe_combine_olmoe(self, one_chip, dtype):
        G, S, D, E, K = 2, 256, 2048, 64, 8
        C = moe_capacity(S, E, K)
        s = lambda sh, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
            sh, dt, sharding=one_chip)
        hlo = _compile(moe_k.combine_pallas, s((G, E, C, D)),
                       s((G, S, K), jnp.int32), s((G, S, K), jnp.int32),
                       s((G, S, K), jnp.float32))
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_embedding_bag_ctr(self, one_chip, dtype):
        """200k x 16 table, 26 slots, batch 256 (the CTR workload)."""
        s = lambda sh, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
            sh, dt, sharding=one_chip)
        hlo = _compile(embedding_bag, s((256, 26), jnp.int32),
                       s((200_000, 16)))
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_flash_attention_llama32_1b(self, one_chip, dtype):
        """32 heads of 64 over a 1024-token prefill."""
        s = lambda sh, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
            sh, dt, sharding=one_chip)
        qkv = [s((1, 32, 1024, 64))] * 3
        assert "tpu_custom_call" in _compile(flash_attention, *qkv)

    @pytest.mark.parametrize("H,KV,S", [(32, 2, 8192), (16, 16, 4096)],
                             ids=["chatglm3-6b-32k", "olmoe-1b-7b"])
    def test_flash_attention_train(self, one_chip, H, KV, S):
        """``jax.grad`` of the kernel at the training cells' widths: the
        forward, dQ and dK/dV passes are each a kernel."""
        s = lambda sh: jax.ShapeDtypeStruct(  # noqa: E731
            sh, jnp.bfloat16, sharding=one_chip)
        loss = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v).astype(jnp.float32).sum()
        hlo = _compile(jax.grad(loss, (0, 1, 2)), s((1, H, S, 128)),
                       s((1, KV, S, 128)), s((1, KV, S, 128)))
        for name in ("flash_fwd", "flash_dq", "flash_dkv"):
            assert re.search(rf"%{name}[.0-9]* = .*tpu_custom_call", hlo), name
