"""The Pallas kernels compile for a TPU v5e, described and not attached.

Interpret mode checks what a kernel computes; only the chip's compiler
checks that its blocks are tile-aligned and that it fits scoped VMEM.  Each
test lowers one kernel variant that the TPU dispatch can choose, at
internlm2-1.8b widths (the paged kernel also at qwen3-8b widths, whose
32 q heads make the widest query tiles of the zoo's attention configs),
and compiles it for one chip of a described ``v5e:2x2``.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.fused_sparse_linear import fused_sparse_linear
from repro.kernels.nm_spmm import nm_spmm
from repro.serving.paged.paged_attention import paged_attention_pallas

N, M, O_N = 8, 16, 16                       # 8:16 + 16:256 outliers
BLOCK = 16                                  # paged KV block size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for one v5e chip; returns the lowered text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()
    return lowered.as_text()


def _weight_shapes(out, kdim, values_dtype):
    return [((out, kdim * N // M), values_dtype), ((out, kdim // M),
                                                   jnp.int32)]


_INTERNLM2 = configs.get("internlm2-1.8b")
# w_up [d_ff, d] and w_down [d, d_ff]: one and four contraction tiles
_WEIGHTS = [(_INTERNLM2.d_ff, _INTERNLM2.d_model),
            (_INTERNLM2.d_model, _INTERNLM2.d_ff)]


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("out,kdim", _WEIGHTS, ids=["w_up", "w_down"])
def test_nm_spmm_compiles(one_chip, out, kdim, quantized):
    vdt = jnp.int8 if quantized else jnp.bfloat16
    shapes = [((8, kdim), jnp.bfloat16)] + _weight_shapes(out, kdim, vdt)
    if quantized:
        shapes.append(((out,), jnp.float32))

    def fn(x, v, meta, *scale):
        return nm_spmm(x, v, meta, n=N, m=M,
                       scale=scale[0] if scale else None, interpret=False)

    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("batch", [8, 512])
def test_fused_sparse_linear_compiles(one_chip, batch, quantized):
    out, kdim = _WEIGHTS[0]
    vdt = jnp.int8 if quantized else jnp.bfloat16
    shapes = ([((batch, kdim), jnp.bfloat16)]
              + _weight_shapes(out, kdim, vdt)
              + [((out, kdim // 256, O_N), jnp.bfloat16),
                 ((out, kdim // 256, O_N // 4), jnp.int32)])
    if quantized:
        shapes.append(((out,), jnp.float32))

    def fn(x, v, meta, ov, om, *scale):
        return fused_sparse_linear(x, v, meta, ov, om, n=N, m=M, o_n=O_N,
                                   scale=scale[0] if scale else None,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


def _paged_shapes(cfg, S, quantized, batch=8, n_blocks=256, per_row=40):
    kv_dt = jnp.int8 if quantized else cfg.dtype
    arena = (n_blocks, BLOCK, cfg.n_kv_heads, cfg.hd)
    shapes = [((batch, S, cfg.n_heads, cfg.hd), cfg.dtype),
              (arena, kv_dt), (arena, kv_dt),
              ((batch, per_row), jnp.int32), ((batch,), jnp.int32)]
    if quantized:
        shapes += [((n_blocks, BLOCK, cfg.n_kv_heads), jnp.float32)] * 2
    return shapes


def _paged_fn(q, k, v, bt, cursor, *scales):
    ks, vs = scales if scales else (None, None)
    return paged_attention_pallas(q, k, v, bt, cursor, interpret=False,
                                  k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("S", [1, 512], ids=["decode", "chunk512"])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen3-8b"])
def test_paged_attention_untiled_compiles(one_chip, arch, S, quantized):
    text = _compile(one_chip, _paged_fn,
                    *_paged_shapes(configs.get(arch), S, quantized))
    assert "tpu_custom_call" in text
