"""The benchmark's own counts of bytes and operations agree with what the
program builds (``SparseWeight.deployed_bytes``, ``ModelConfig.param_count``)
for every configuration in ``BENCHMARK.json``, and the counting functions
do what their formulas say."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import counting, weights

# every configuration file
FILES = sorted((Path(__file__).parents[1] / "configs").glob("*.json"))
CONFIGS = {p.stem: json.loads(p.read_text()) for p in FILES}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_count_matches_program(name):
    from bench.harness import model_config
    conf = CONFIGS[name]
    assert counting.param_count(conf) == model_config(conf).param_count()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weight_bytes_match_deployed_buffers(name):
    """Each projection's counted bytes equal the deployed buffers of the
    SparseWeight the program makes from it (shapes only: eval_shape)."""
    from bench.harness import compressor
    conf = CONFIGS[name]
    m, wfmt = conf["model"], conf["weights"]
    comp = compressor(wfmt)
    for pname, (o, i) in weights.proj_shapes(m).items():
        w = jax.ShapeDtypeStruct((o, i), jnp.dtype(m["dtype"]))
        sw = jax.eval_shape(comp, w)
        deployed = sum(x.size * x.dtype.itemsize for x in (
            sw.nm_values, sw.nm_meta, sw.o_values, sw.o_meta, sw.v_scale)
            if x is not None)
        assert counting.proj_bytes(o, i, wfmt, m["dtype"]) == deployed, pname


def test_internlm2_projection_bytes_as_measured_on_the_chip():
    """The 7 projection kinds compress 3019898880 -> 2170552320 B, as the
    program reported on the chip."""
    conf = CONFIGS["internlm2-1.8b.s816"]
    wb = counting.weight_bytes(conf)
    assert sum(wb[k] for k in weights.PROJ) == 2170552320
    dense = dict(conf, weights={"format": "dense"})
    assert sum(counting.weight_bytes(dense)[k] for k in weights.PROJ) \
        == 3019898880


@pytest.mark.parametrize("values", ["bfloat16", "int8"])
def test_proj_bytes_int8_counts_row_scales(values):
    wfmt = {"format": "nm_outlier", "weight_pattern": "8:16",
            "outlier_pattern": "16:256", "values": values}
    b = counting.proj_bytes(256, 512, wfmt, "bfloat16")
    it = 2 if values == "bfloat16" else 1
    expect = (256 * 256 * it + 256 * 32 * 4 + 256 * 2 * 16 * 2
              + 256 * 2 * 4 * 4 + (256 * 4 if values == "int8" else 0))
    assert b == expect


def test_flops_count_nonzeros_attention_and_head():
    conf = {"model": {"n_layers": 2, "d_model": 32, "n_heads": 4,
                      "n_kv_heads": 2, "head_dim": 8, "d_ff": 64, "vocab": 100,
                      "window": 3, "dtype": "bfloat16",
                      "tie_embeddings": False},
            "weights": {"format": "dense"}}
    per_tok = 2 * 2 * (32 * 32 + 2 * 16 * 32 + 32 * 32 + 3 * 64 * 32)
    assert counting.token_flops(conf) == per_tok
    # a query at position 5 sees min(6, window 3) keys
    assert counting.attention_flops(conf, 5) == 4 * 4 * 8 * 3 * 2
    f = counting.call_flops(conf, [(0, 2)], heads=1)
    assert f == (2 * 100 * 32 + 2 * per_tok
                 + counting.attention_flops(conf, 0)
                 + counting.attention_flops(conf, 1))


def test_decode_bytes_cover_weights_and_kv():
    conf = CONFIGS["internlm2-1.8b.s816"]
    wb = counting.weight_bytes(conf)
    kv = counting.kv_bytes_per_token(conf)
    assert kv == 2 * 24 * 8 * 128 * 2
    b = counting.decode_bytes(conf, [(99, 1), (9, 1)])
    no_embed = sum(v for k, v in wb.items() if k != "embed")
    assert b == no_embed + 2 * 2048 * 2 + kv * (100 + 1) + kv * (10 + 1)
    pb, pf = counting.paged_attention_decode(conf, [(99, 1)])
    assert pb == 24 * (2 * 16 * 128 * 2 + 2 * 100 * 8 * 128 * 2)
    assert pf == counting.attention_flops(conf, 99)
