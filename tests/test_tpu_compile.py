"""Compile-only checks for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that is
described and not attached.  This refuses what interpret mode accepts: block
shapes off the (8, 128) tiling, slices Mosaic cannot lower, programs larger
than the chip's memory.  Each kernel of the main path is compiled with
``interpret=False`` at qwen1.5-0.5b's published widths (rwkv6-1.6b's for
``wkv6``), and so is the fused decode tick.  Nothing runs: no result or
time comes from these tests.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and a test
worker that did so at import would leave the others nothing to collect.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_wkv import wkv6
from repro.models.kvcache import init_cache, init_paged_cache
from repro.models.layers import apply_attention, init_attention
from repro.models.ssm import rwkv_dims
from repro.models.transformer import init_model
from repro.serving.engine import balanced_boundaries
from repro.serving.executor_cache import ExecutorCache

V5E_HBM_BYTES = 16e9
QWEN = get_arch("qwen1.5-0.5b").config
QWEN_110B = get_arch("qwen1.5-110b").config
B = 8                      # the engine's max_batch on the chip
SMAX = 2048                # dense cache rows per slot
BLOCK = 16                 # paged block size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _kernel_case(name, dt):
    H, Kh, hd = QWEN.n_heads, QWEN.n_kv_heads, QWEN.resolved_head_dim
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    if name == "paged_decode_attention":
        n_blocks = 1 + B * SMAX // BLOCK
        return (lambda q, k, v, bt, cl: paged_decode_attention(
                    q, k, v, bt, cl, interpret=False),
                [S((B, H, hd), dt), S((n_blocks, Kh, BLOCK, hd), dt),
                 S((n_blocks, Kh, BLOCK, hd), dt),
                 S((B, SMAX // BLOCK), i32), S((B,), i32)])
    if name == "decode_attention":
        return (lambda q, k, v, cl: decode_attention(q, k, v, cl,
                                                     interpret=False),
                [S((B, H, hd), dt), S((B, Kh, SMAX, hd), dt),
                 S((B, Kh, SMAX, hd), dt), S((B,), i32)])
    if name == "flash_attention":
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
                [S((1, 512, H, hd), dt), S((1, 512, Kh, hd), dt),
                 S((1, 512, Kh, hd), dt)])
    Hr, hs = rwkv_dims(get_arch("rwkv6-1.6b").config)
    return (lambda r, k, v, w, u: wkv6(r, k, v, w, u, interpret=False),
            [S((1, 256, Hr, hs), dt)] * 4 + [S((Hr, hs), dt)])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["paged_decode_attention",
                                  "decode_attention", "flash_attention",
                                  "wkv6"])
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, args = _kernel_case(name, dtype)
    compiled = jax.jit(fn).lower(*_on(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fused_tick(sharding, *, paged, batch, stages):
    """The fused decode tick at qwen1.5-0.5b's widths in bf16, lowered for
    ``sharding``, and the shape of one cache leaf."""
    bf16 = jnp.bfloat16
    params = _on(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), QWEN, bf16)), sharding)
    ex = ExecutorCache(QWEN, params, max_batch=batch, max_seq=SMAX,
                       cache_dtype=bf16, paged=paged, paged_kernel=paged)
    prog, _ = ex.fused_decode(balanced_boundaries(QWEN.n_layers, stages))
    if paged:
        caches = init_paged_cache(QWEN, 1 + batch * SMAX // BLOCK, BLOCK,
                                  bf16, materialize=False)
        tables = jax.ShapeDtypeStruct((batch, SMAX // BLOCK), jnp.int32)
    else:
        caches = init_cache(QWEN, batch, SMAX, bf16, materialize=False)
        tables = None
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
    leaf = jax.tree.leaves(caches)[0].shape
    return prog.lower(*_on((caches, tok, pos, tables), sharding)), leaf


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_fused_tick_fits_v5e(one_chip, paged):
    """The 6-stage fused decode tick at published widths in bf16 compiles
    for one v5e and fits its memory.  The paged tick runs the table-walk
    kernel with the engine's default ``interpret=None``, which must resolve
    to the Mosaic kernel when the program is built for a TPU."""
    lowered, _ = _fused_tick(one_chip, paged=paged, batch=B, stages=6)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < V5E_HBM_BYTES, ma
    assert ("tpu_custom_call" in compiled.as_text()) == paged


def _dense_tick(sharding):
    """The 0.5B cell's tick: 4 stages, batch 32."""
    return _fused_tick(sharding, paged=False, batch=32, stages=4)


def _paged_layer(sharding):
    """One attention layer's paged decode step (table-walk kernel) at
    qwen1.5-110b's widths, on a pool of 16 slots x 4096 rows."""
    cfg, bf16, batch, max_seq = QWEN_110B, jnp.bfloat16, 16, 4096
    S = jax.ShapeDtypeStruct
    leaf = (1 + batch * max_seq // BLOCK, cfg.n_kv_heads, BLOCK,
            cfg.resolved_head_dim)

    def step(params, x, cache, tables, pos):
        y, new_cache, _ = apply_attention(cfg, params, x, pos0=pos,
                                          cache=cache, block_table=tables,
                                          paged_kernel=True)
        return y, new_cache

    params = jax.eval_shape(
        lambda: init_attention(jax.random.PRNGKey(0), cfg, bf16))
    args = (params, S((batch, 1, cfg.d_model), bf16),
            {"k": S(leaf, bf16), "v": S(leaf, bf16)},
            S((batch, max_seq // BLOCK), jnp.int32), S((batch,), jnp.int32))
    return jax.jit(step, donate_argnums=(2,)).lower(*_on(args, sharding)), leaf


def _copies_of(hlo_text, shape):
    """Copy instructions in compiled HLO whose result has ``shape``."""
    dims = "[" + ",".join(map(str, shape)) + "]"
    return [line for line in hlo_text.splitlines()
            if (m := re.search(r"= (.+?) (copy|copy-start)\(", line))
            and dims in m.group(1)]


@pytest.mark.parametrize("build", [_dense_tick, _paged_layer],
                         ids=["dense_tick", "paged_layer"])
def test_kv_write_copies_no_cache_leaf(one_chip, build):
    """Writing the decode tick's K/V rows copies no cache leaf.

    A scatter whose window straddles its indexed dim makes XLA copy each
    leaf into another layout and back around the write: 96 copies of
    bf16[32,16,2048,64] a tick on the dense cache, 4 of
    bf16[4097,8,16,128] a layer on the pool.  Only the paged case at head
    dim 128 is held here: at head dim 64 the table-walk kernel's own
    operand layout forces copies whatever the write does, a separate
    matter from the write.
    """
    lowered, leaf = build(one_chip)
    copies = _copies_of(lowered.compile().as_text(), leaf)
    assert not copies, copies[:4]
