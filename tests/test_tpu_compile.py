"""The Pallas kernels compile for a TPU v5e, described and not attached.

Interpret mode checks what a kernel computes; only the chip's compiler
checks that its blocks are tile-aligned and that it fits scoped VMEM.  Each
test lowers one kernel variant that the TPU dispatch can choose, at
internlm2-1.8b widths (the sparse linear also at Mistral-7B's and
LLaMA-2-7B/13B's, the paged kernel also at qwen3-8b widths, whose
32 q heads make the widest query tiles of the zoo's attention configs),
and compiles it for one chip of a described ``v5e:2x2``.  Two more compile a
whole step program, Mistral-7B's decode and chunk steps at published
widths, and read the compiler's memory analysis.  Nothing runs.

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
        return fused_sparse_linear(x, v, meta, n=N, m=M,
                                   scale=scale[0] if scale else None,
                                   interpret=False)

    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


_MISTRAL = configs.get("mistral-7b")


def _compile_fused(one_chip, out, kdim, batch, quantized):
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

    return _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("batch", [8, 512, 4096])
def test_fused_sparse_linear_compiles(one_chip, batch, quantized):
    out, kdim = _WEIGHTS[0]
    assert "tpu_custom_call" in _compile_fused(one_chip, out, kdim, batch,
                                               quantized)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("batch", [8, 4096])
@pytest.mark.parametrize("out,kdim", [(_MISTRAL.d_ff, _MISTRAL.d_model),
                                      (_MISTRAL.d_model, _MISTRAL.d_ff)],
                         ids=["w_gate", "w_down"])
def test_fused_sparse_linear_compiles_mistral(one_chip, out, kdim, batch,
                                              quantized):
    assert "tpu_custom_call" in _compile_fused(one_chip, out, kdim, batch,
                                               quantized)


_LLAMA2_7B, _LLAMA2_13B = configs.get("llama2-7b"), configs.get("llama2-13b")


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("batch", [4096])
@pytest.mark.parametrize("out,kdim", [
    (_LLAMA2_7B.d_model, _LLAMA2_7B.d_ff),
    (_LLAMA2_13B.d_model, _LLAMA2_13B.d_ff)],
    ids=["7b_w_down", "13b_w_down"])
def test_fused_sparse_linear_compiles_llama2(one_chip, out, kdim, batch,
                                             quantized, monkeypatch):
    """The paper's LLaMA-2 widths, whose contraction dims 2048 does not
    divide (11008, 13824), so ``k_tile`` takes them whole and the
    kernel narrows its out and row tiles: each compiles within a third of
    the scoped VMEM the kernel asks for (the 13B w_down at 4096 rows took
    86 MiB with the tiles a 2048-wide k tile uses)."""
    from repro.kernels import fused_sparse_linear as fsl
    monkeypatch.setattr(fsl, "VMEM_LIMIT", fsl.VMEM_LIMIT // 3)
    assert "tpu_custom_call" in _compile_fused(one_chip, out, kdim, batch,
                                               quantized)


def _paged_shapes(cfg, S, quantized, batch=8, n_blocks=256, per_row=40,
                  n_layers=4):
    kv_dt = jnp.int8 if quantized else cfg.dtype
    arena = (n_layers, n_blocks, BLOCK, cfg.n_kv_heads, cfg.hd)
    shapes = [((batch, S, cfg.n_heads, cfg.hd), cfg.dtype),
              (arena, kv_dt), (arena, kv_dt),
              ((batch, per_row), jnp.int32), ((batch,), jnp.int32),
              ((), jnp.int32)]
    if quantized:
        shapes += [(arena[:-1], jnp.float32)] * 2
    return shapes


def _paged_fn(q, k, v, bt, cursor, layer, *scales):
    ks, vs = scales if scales else (None, None)
    return paged_attention_pallas(q, k, v, bt, cursor, layer=layer,
                                  interpret=False, k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("S", [1, 512], ids=["decode", "chunk512"])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen3-8b"])
def test_paged_attention_untiled_compiles(one_chip, arch, S, quantized):
    text = _compile(one_chip, _paged_fn,
                    *_paged_shapes(configs.get(arch), S, quantized))
    assert "tpu_custom_call" in text


# the HBM one v5e chip offers to a program (``memory_stats`` on the chip)
V5E_HBM = 16_909_336_064
HEADROOM = 0.5e9


@pytest.mark.parametrize("S", [1, 2048], ids=["decode", "chunk2048"])
def test_mistral_7b_step_fits_one_chip(one_chip, S, monkeypatch):
    """Mistral-7B at published widths, 8:16 + 16:256 (the benchmark's
    ``mistral-7b.s816``), with its whole paged arena (2048 blocks of 16
    plus the trash block): the decode step over its 8 slots and a
    2048-token chunk step compile for one chip, leave at least
    ``HEADROOM`` of its HBM free beside their arguments, temporaries and
    outputs, copy no whole arena or layer of it (the arena is written in
    place), and serve every projection through the ``sparse_linear``
    kernel with no dense [out, in] weight rebuilt."""
    import re
    from bench import harness, registry, weights
    # dispatch as on the chip: the kernels compile, the sparse linear
    # takes its kernel branch (this host's backend is the CPU)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from repro.models import transformer as tfm
    from repro.serving import families
    from repro.serving.paged import PagedPoolView
    from repro.serving.placement import ServingPlacement
    conf = registry.config("mistral-7b.s816")
    m, dep = conf["model"], conf["deployment"]
    cfg = harness.model_config(conf)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(
        weights.params_fn(m, harness.compressor(conf["weights"])),
        jax.random.PRNGKey(0)))
    layer = (dep["n_blocks"] + 1, dep["block_size"], m["n_kv_heads"],
             m["head_dim"])
    arena = sds((m["n_layers"], *layer), jnp.bfloat16)
    nb = dep["max_len"] // dep["block_size"]
    B = dep["n_slots"] if S == 1 else 1
    step = families._jit(
        ServingPlacement(),
        lambda p, k, v, bt, cur, nn, t: tfm.unified_step(
            p, PagedPoolView(k, v, bt, cur, nn, 0), {"tokens": t}, cfg,
            attn_backend="pallas"), donate=(1, 2))
    rows = sds((B,), jnp.int32)
    compiled = step.lower(params, arena, arena, sds((B, nb), jnp.int32),
                          rows, rows, sds((B, S), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used + HEADROOM <= V5E_HBM, used
    whole = re.compile(r"= \S+\[(\d+,)?%s\]\S* (copy|dynamic-slice|"
                       r"dynamic-update-slice)\(" % ",".join(map(str, layer)))
    text = compiled.as_text().splitlines()
    assert not [ln for ln in text if whole.search(ln)]
    assert [ln for ln in text if re.search(
        r"%sparse_linear\S* = .*custom-call\(.*tpu_custom_call", ln)]
    d, f, q, kv = (m["d_model"], m["d_ff"], m["n_heads"] * m["head_dim"],
                   m["n_kv_heads"] * m["head_dim"])
    dense = re.compile(r"= \w+\[(%s)\]" % "|".join(
        f"{o},{i}" for o, i in {(f, d), (d, f), (q, d), (d, q), (kv, d)}))
    assert not [ln for ln in text if dense.search(ln)]
