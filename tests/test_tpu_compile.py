"""Compile every Pallas kernel of the serving path for a TPU v5e chip.

The chip is described, not attached (``jax.experimental.topologies``), so
these tests run on the CPU host: the TPU compiler lowers each kernel with
interpret mode off and refuses what the chip would refuse (tiles that do
not divide the (8, 128) layout, more VMEM than a kernel may use).  Shapes
are the paper target's attention heads (Llama-3.1-70B: 64 query heads,
8 KV heads, head_dim 128) at the serving defaults: 4 KV slots, tree
width 8, a 512-row model cache, a 73-row tree buffer
(``PipeDecConfig(n_stages=4, width=8).tree_buffer_capacity``), 16-row
pages, and the q-projection width for the int8 matmul.

This is the only file that describes the topology.  The description
happens inside a fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker running this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash import flash_attention_lse
from repro.kernels.paged import (paged_flash_attention_lse,
                                 paged_tree_block_attention)
from repro.kernels.quant import dequant_matmul_kernel
from repro.kernels.tree_block import tree_block_attention

B, H, KV, HD = 4, 64, 8, 128          # slots, target heads, KV heads, dim
N, L, T, PAGE = 8, 512, 73, 16        # tree width, cache rows, tree rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Lower + compile ``fn`` for the described chip; returns the
    compiled executable's HLO text (raises what the TPU compiler
    raises)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo):
    assert "tpu_custom_call" in hlo, "the Pallas kernel was not lowered"


def test_flash_compiles_for_v5e(one_chip):
    hlo = _compile(
        lambda q, k, v, n: flash_attention_lse(q, k, v, n, interpret=False),
        one_chip, ((B, H, N, HD), jnp.float32), ((B, KV, L, HD), jnp.float32),
        ((B, KV, L, HD), jnp.float32), ((B,), jnp.int32))
    _assert_kernel(hlo)


def test_tree_block_compiles_for_v5e(one_chip):
    hlo = _compile(
        lambda q, k, v, m: tree_block_attention(q, k, v, m, interpret=False),
        one_chip, ((B, H, N, HD), jnp.float32), ((B, KV, T, HD), jnp.float32),
        ((B, KV, T, HD), jnp.float32), ((B, N, T), jnp.bool_))
    _assert_kernel(hlo)


def test_paged_flash_compiles_for_v5e(one_chip):
    mb = L // PAGE
    hlo = _compile(
        lambda q, k, v, tab, n: paged_flash_attention_lse(
            q, k, v, tab, n, interpret=False),
        one_chip, ((B, H, N, HD), jnp.float32),
        ((1 + B * mb, KV, PAGE, HD), jnp.float32),
        ((1 + B * mb, KV, PAGE, HD), jnp.float32), ((B, mb), jnp.int32),
        ((B,), jnp.int32))
    _assert_kernel(hlo)


def test_paged_tree_compiles_for_v5e(one_chip):
    mb = -(-T // PAGE)
    hlo = _compile(
        lambda q, k, v, tab, m: paged_tree_block_attention(
            q, k, v, tab, m, interpret=False),
        one_chip, ((B, H, N, HD), jnp.float32),
        ((1 + B * mb, KV, PAGE, HD), jnp.float32),
        ((1 + B * mb, KV, PAGE, HD), jnp.float32), ((B, mb), jnp.int32),
        ((B, N, T), jnp.bool_))
    _assert_kernel(hlo)


def test_dequant_matmul_compiles_for_v5e(one_chip):
    d = H * HD
    hlo = _compile(
        lambda x, w, s: dequant_matmul_kernel(x, w, s, interpret=False),
        one_chip, ((B * N, d), jnp.float32), ((d, d), jnp.int8),
        ((d,), jnp.float32))
    _assert_kernel(hlo)
