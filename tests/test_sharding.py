"""Sharding-rule unit tests (no 512-device env needed: 4-device host mesh)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import (param_spec, batch_spec, cache_spec,
                                     fsdp_axes, sparse_weight_specs)


@pytest.fixture(scope="module")
def mesh():
    # a virtual (4, 4) mesh: spec resolution only needs axis SIZES
    devs = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    return jax.sharding.Mesh(devs, ("data", "model"))


class TestParamSpecs:
    def test_column_parallel(self, mesh):
        assert param_spec(mesh, "layers/wq", (24, 2048, 4096)) == \
            P(None, "model", ("data",))

    def test_row_parallel(self, mesh):
        assert param_spec(mesh, "layers/wo", (24, 4096, 2048)) == \
            P(None, ("data",), "model")

    def test_norms_replicated(self, mesh):
        assert param_spec(mesh, "layers/attn_norm", (24, 4096)) == P(None, None)

    def test_embed(self, mesh):
        assert param_spec(mesh, "embed", (32000, 4096)) == P("model", ("data",))

    def test_divisibility_fallback(self, mesh):
        # whisper vocab 51865 is not divisible by 4 -> vocab dim replicated
        sp = param_spec(mesh, "embed", (51865, 1024))
        assert sp == P(None, ("data",))

    def test_moe_expert_ep_layout(self, mesh):
        # 128 experts divisible by fsdp=4 -> experts over data, ff over model
        assert param_spec(mesh, "layers/moe/we_gate", (48, 128, 5120, 8192)) == \
            P(None, ("data",), None, "model")
        # 6 experts not divisible -> fallback TP-only
        sp = param_spec(mesh, "layers/moe/we_gate", (48, 6, 5120, 8192))
        assert sp == P(None, None, ("data",), "model")

    def test_router_replicated(self, mesh):
        assert param_spec(mesh, "layers/moe/router", (48, 128, 5120)) == \
            P(None, None, None)


def _sw(out, in_dim, m=16, n=8, o_n=0, quantized=False, L=None):
    """SparseWeight of ShapeDtypeStructs (specs only need shapes+statics)."""
    from repro.models.sparse_serving import SparseWeight
    lead = () if L is None else (L,)
    sds = jax.ShapeDtypeStruct
    vdt = jnp.int8 if quantized else jnp.bfloat16
    return SparseWeight(
        nm_values=sds((*lead, out, in_dim * n // m), vdt),
        nm_meta=sds((*lead, out, in_dim // m), jnp.int32),
        o_values=None if o_n == 0 else
        sds((*lead, out, in_dim // 256, o_n), jnp.bfloat16),
        o_meta=None if o_n == 0 else
        sds((*lead, out, in_dim // 256, o_n // 4), jnp.int32),
        v_scale=None if not quantized else sds((*lead, out), jnp.float32),
        n=n, m=m, o_n=o_n, in_dim=in_dim)


class TestSparseWeightSpecs:
    """Mesh-aware placement of compressed containers: out-dim (row)
    sharding is always safe; in-dim sharding must land on N:M-block and
    256-wide outlier-group boundaries or fall back to replication."""

    def test_aligned_in_dim_shards_over_fsdp(self, mesh):
        # fsdp=4, m=16: in_dim 256 % (4*16) == 0 -> values+meta in over data
        sp = sparse_weight_specs(mesh, _sw(64, 256))
        assert sp.nm_values == P("model", ("data",))
        assert sp.nm_meta == P("model", ("data",))

    def test_in_dim_splitting_nm_block_replicates(self, mesh):
        # in_dim 48 is 16-aligned but 48 % (4*16) != 0: a data-shard
        # boundary would land inside an N:M block.  The raw compressed dim
        # (48*8/16 = 24) DOES divide 4 — divisibility alone must not win.
        sp = sparse_weight_specs(mesh, _sw(64, 48))
        assert sp.nm_values[-1] is None and sp.nm_meta[-1] is None
        # out dim may absorb fsdp instead (64 % (4*4) == 0)
        assert sp.nm_values[0] == ("model", "data")

    def test_in_dim_splitting_outlier_group_replicates(self, mesh):
        # 512 % (4*16) == 0 but 512 % (4*256) != 0: fine without outliers,
        # rejected with them (a shard edge would cut a 256-wide group)
        no_outliers = sparse_weight_specs(mesh, _sw(4, 512))
        assert no_outliers.nm_values[-1] == "data"
        with_outliers = sparse_weight_specs(mesh, _sw(4, 512, o_n=16))
        assert with_outliers.nm_values[-1] is None
        assert with_outliers.o_values == P("model", None, None)

    def test_replication_fallback_when_nothing_divides(self, mesh):
        # out 4 % model(4) == 0 but 4 % (model*fsdp)=16 != 0: no fsdp fold
        sp = sparse_weight_specs(mesh, _sw(4, 48))
        assert sp.nm_values == P("model", None)

    def test_metadata_and_scales_coshard_with_values(self, mesh):
        sp = sparse_weight_specs(mesh, _sw(64, 1024, o_n=16, quantized=True,
                                           L=2))
        assert sp.nm_meta == sp.nm_values == P(None, "model", ("data",))
        assert sp.o_values == sp.o_meta == P(None, "model", ("data",), None)
        assert sp.v_scale == P(None, "model")     # same out axes

    def test_serving_policy_never_shards_contractions(self, mesh):
        # serving placement: out-dim TP only (token-stream parity)
        sp = sparse_weight_specs(mesh, _sw(64, 256, o_n=16), serving=True)
        assert sp.nm_values == P("model", None)
        assert sp.o_values == P("model", None, None)


class TestBatchCacheSpecs:
    def test_tokens(self, mesh):
        assert batch_spec(mesh, (256, 4096)) == P(("data",), None)

    def test_mrope_positions(self, mesh):
        assert batch_spec(mesh, (3, 256, 4096)) == P(None, ("data",), None)

    def test_seq_shard_for_batch1(self, mesh):
        assert batch_spec(mesh, (1, 524288), seq_shard=True) == \
            P(None, ("data",))

    def test_kv_cache_head_sharded_when_divisible(self, mesh):
        assert cache_spec(mesh, "k", (24, 128, 32768, 16, 64)) == \
            P(None, ("data",), None, "model", None)

    def test_kv_cache_seq_sharded_for_gqa(self, mesh):
        # kv=2 < model axis 4 -> flash-decoding layout (seq over model)
        assert cache_spec(mesh, "k", (24, 128, 32768, 2, 64)) == \
            P(None, ("data",), "model", None, None)

    def test_pos_scalar(self, mesh):
        assert cache_spec(mesh, "pos", ()) == P()


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """End-to-end dry-run of one small cell in a fresh 512-device process."""
    code = (
        "import sys; sys.path.insert(0, 'src')\n"
        "from repro.launch.dryrun import run_cell\n"
        "rec = run_cell('internlm2-1.8b', 'decode_32k', multi_pod=False,"
        " probe=False, out_dir=None, verbose=False)\n"
        "assert rec['status'] == 'ok', rec\n"
        "assert rec['full']['collective_bytes']['total'] > 0\n"
        "print('CELL_OK')\n"
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
                         env=env, timeout=900)
    assert "CELL_OK" in out.stdout, out.stdout + out.stderr
