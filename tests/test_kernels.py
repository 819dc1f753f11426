"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
the kernel against the portable path of ``sparse_apply``, the choice
between them, and the kernel's traced size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import (ActStats, SparsifyConfig, sparsify_linear,
                        dense_effective_weight, pack_nm, nm_mask)
from repro.core.packing import pack_outlier_meta, unpack_outlier_meta
from repro.kernels import ref
from repro.kernels.fused_sparse_linear import fused_sparse_linear


def _packed(key, out, kdim, n, m, dtype):
    w = jax.random.normal(key, (out, kdim), jnp.float32).astype(dtype)
    mask = nm_mask(jnp.abs(w.astype(jnp.float32)), (n, m))
    return pack_nm(jnp.where(mask, w, 0), mask, (n, m))


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-1)}


class TestNmSpmm:
    @pytest.mark.parametrize("n,m", [(2, 4), (4, 8), (8, 16)])
    @pytest.mark.parametrize("b,out,kdim", [(8, 64, 256), (32, 128, 512),
                                            (128, 256, 1024)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vs_ref(self, n, m, b, out, kdim, dtype):
        pk = _packed(jax.random.PRNGKey(0), out, kdim, n, m, dtype)
        x = jax.random.normal(jax.random.PRNGKey(1), (b, kdim)).astype(dtype)
        y_ref = ref.nm_spmm_ref(x, pk.values, pk.indices, m)
        y = fused_sparse_linear(x, pk.values, pk.packed_metadata(), n=n, m=m,
                                block_b=64, block_o=64, block_k=256)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), **TOL[dtype])

    def test_vs_dense_matmul(self):
        """Compressed matmul == dense matmul with the pruned matrix."""
        pk = _packed(jax.random.PRNGKey(2), 64, 512, 8, 16, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 512))
        y_dense = x @ pk.to_dense().T
        y = fused_sparse_linear(x, pk.values, pk.packed_metadata(), n=8,
                                m=16, block_b=16, block_o=64, block_k=256)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   rtol=2e-5, atol=2e-4)


class TestOutlierSpmm:
    @pytest.mark.parametrize("o_n", [4, 8, 16])
    @pytest.mark.parametrize("b,out,kdim", [(8, 64, 256), (16, 128, 512)])
    def test_vs_ref(self, o_n, b, out, kdim):
        """The kernel's outlier stream alone (its N:M values zeroed)
        against the outlier oracle."""
        w = jax.random.normal(jax.random.PRNGKey(0), (out, kdim))
        x = jax.random.normal(jax.random.PRNGKey(1), (b, kdim))
        st = ActStats.init(kdim).update(x)
        cfg = SparsifyConfig(weight_pattern="8:16", outlier_pattern=f"{o_n}:256")
        sl = sparsify_linear(w, st, cfg)
        y_ref = ref.outlier_spmm_ref(x, sl.outliers.values, sl.outliers.indices)
        y = fused_sparse_linear(x, jnp.zeros_like(sl.nm.values),
                                sl.nm.packed_metadata(), sl.outliers.values,
                                pack_outlier_meta(sl.outliers.indices),
                                n=8, m=16, o_n=o_n, block_b=8, block_o=64,
                                block_k=256)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-4)

    def test_meta_roundtrip(self):
        idx = jax.random.randint(jax.random.PRNGKey(0), (4, 2, 16), 0, 256)
        idx = jnp.sort(idx, axis=-1)
        packed = pack_outlier_meta(idx)
        assert packed.shape == (4, 2, 4)
        np.testing.assert_array_equal(np.asarray(unpack_outlier_meta(packed, 16)),
                                      np.asarray(idx))


class TestFused:
    @pytest.mark.parametrize("n,m,o_n", [(2, 4, 4), (8, 16, 16), (4, 8, 8)])
    def test_fused_equals_dense_effective(self, n, m, o_n):
        """Fused kernel output == x @ (deployed dense-effective weight)^T."""
        w = jax.random.normal(jax.random.PRNGKey(0), (128, 512))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 512))
        st = ActStats.init(512).update(x)
        cfg = SparsifyConfig(weight_pattern=f"{n}:{m}",
                             outlier_pattern=f"{o_n}:256")
        sl = sparsify_linear(w, st, cfg)
        eff = dense_effective_weight(w, sl, cfg)
        y_dense = x @ eff.T
        y = fused_sparse_linear(x, sl.nm.values, sl.nm.packed_metadata(),
                                sl.outliers.values,
                                pack_outlier_meta(sl.outliers.indices),
                                n=n, m=m, o_n=o_n,
                                block_b=16, block_o=64, block_k=256)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   rtol=2e-5, atol=5e-4)

    def test_ops_backends_agree(self):
        """``sparse_apply``'s two branches, the kernel and the portable
        one-hot rebuild, compute the same product."""
        from repro.models.sparse_serving import (_to_sparse_weight,
                                                 sparse_apply_pallas,
                                                 sparse_apply_portable)
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 512))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 512))
        st = ActStats.init(512).update(x)
        sw = _to_sparse_weight(w, SparsifyConfig(), stats=st)
        np.testing.assert_allclose(np.asarray(sparse_apply_pallas(sw, x)),
                                   np.asarray(sparse_apply_portable(sw, x)),
                                   rtol=2e-5, atol=5e-4)


# served projection shapes [out, in]: internlm2-1.8b and Mistral-7B
SERVED = [(8192, 2048), (2048, 8192), (2048, 2048), (1024, 2048),
          (14336, 4096), (4096, 14336), (4096, 4096), (1024, 4096)]


def _weight_shapes(out, kdim, quantize=False, outliers=True, o_n=16):
    """A SparseWeight of shapes only, in the stored format."""
    from repro.models.sparse_serving import SparseWeight
    sds = jax.ShapeDtypeStruct
    return SparseWeight(
        nm_values=sds((out, kdim // 2), jnp.int8 if quantize
                      else jnp.bfloat16),
        nm_meta=sds((out, kdim // 16), jnp.int32),
        o_values=sds((out, kdim // 256, o_n), jnp.bfloat16) if outliers
        else None,
        o_meta=sds((out, kdim // 256, o_n // 4), jnp.int32) if outliers
        else None,
        v_scale=sds((out,), jnp.float32) if quantize else None,
        n=8, m=16, o_n=o_n if outliers else 0, in_dim=kdim)


def _kernel_eqns(closed) -> int:
    """Equations of the one Pallas kernel body in a traced program,
    counted through every nested jaxpr (loops, conditionals)."""
    from jax.extend import core as jcore

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield sub.jaxpr
                elif isinstance(sub, jcore.Jaxpr):
                    yield sub

    def count(jaxpr):
        return sum(1 + sum(count(j) for j in subjaxprs(e))
                   for e in jaxpr.eqns)

    def kernels(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["jaxpr"]
            else:
                for j in subjaxprs(e):
                    yield from kernels(j)

    (body,) = kernels(closed.jaxpr)
    return count(body)


class TestSparseServing:
    def test_sparse_weight_matches_dense_effective(self):
        from repro.models.sparse_serving import (_to_sparse_weight,
                                                 sparse_apply,
                                                 sparse_apply_pallas)
        w = jax.random.normal(jax.random.PRNGKey(0), (128, 512))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 512))
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
        sw = _to_sparse_weight(w, cfg)
        sl = sparsify_linear(w, None, cfg)
        eff = dense_effective_weight(w, sl, cfg)
        y_dense = x @ eff.T
        np.testing.assert_allclose(np.asarray(sparse_apply(sw, x)),
                                   np.asarray(y_dense), rtol=2e-5, atol=5e-4)
        np.testing.assert_allclose(np.asarray(sparse_apply_pallas(sw, x)),
                                   np.asarray(y_dense), rtol=2e-5, atol=5e-4)

    @pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
    @pytest.mark.parametrize("outliers", [True, False],
                             ids=["outliers", "nm"])
    @pytest.mark.parametrize("rows", [1, 7, 32, 40])
    def test_kernel_equals_portable(self, rows, outliers, quantize):
        """The kernel on bf16 rows equals the portable path: over two
        128-lane slabs of one out tile and two k tiles, at 1, 7, 32 and 40
        rows in 16-row tiles (1, 7 and 40 padded; 32 and 40 take two and
        three row tiles, which reuse the tile decompressed for the first)."""
        from repro.models.sparse_serving import (_to_sparse_weight,
                                                 sparse_apply_portable)
        w = jax.random.normal(jax.random.PRNGKey(0), (256, 512)) * 0.05
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 512)
                              ).astype(jnp.bfloat16)
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False,
                             outlier_pattern="16:256" if outliers else None)
        sw = _to_sparse_weight(w.astype(jnp.bfloat16), cfg,
                               quantize=quantize)
        y = fused_sparse_linear(
            x, sw.nm_values, sw.nm_meta, sw.o_values, sw.o_meta, n=sw.n,
            m=sw.m, o_n=sw.o_n, scale=sw.v_scale, block_b=16, block_o=256,
            block_k=256)
        assert y.shape == (rows, 256) and y.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(y, np.float32),
            np.asarray(sparse_apply_portable(sw, x), np.float32),
            **TOL[jnp.bfloat16])

    @pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
    def test_whole_contraction_equals_portable(self, quantize):
        """A contraction dim that 2048 does not divide is one k tile, with
        the out and row tiles narrowed (two of each here), and equals the
        portable path."""
        from repro.models.sparse_serving import (_to_sparse_weight,
                                                 sparse_apply_portable)
        w = jax.random.normal(jax.random.PRNGKey(0), (256, 2304)) * 0.05
        x = jax.random.normal(jax.random.PRNGKey(1), (512, 2304)
                              ).astype(jnp.bfloat16)
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
        sw = _to_sparse_weight(w.astype(jnp.bfloat16), cfg,
                               quantize=quantize)
        y = fused_sparse_linear(x, sw.nm_values, sw.nm_meta, sw.o_values,
                                sw.o_meta, n=sw.n, m=sw.m, o_n=sw.o_n,
                                scale=sw.v_scale)
        np.testing.assert_allclose(
            np.asarray(y, np.float32),
            np.asarray(sparse_apply_portable(sw, x), np.float32),
            **TOL[jnp.bfloat16])

    def test_kernel_traced_size_is_constant(self):
        """Every step program traces and lowers the kernel once per
        projection in every process, before the compile cache is looked
        up, so its body must stay small: at internlm2's w_up with outliers
        it has under 200 equations (an unrolled body had over 2000), and
        the same count at 32 outliers per group and at either out tile."""
        def eqns(o_n, block_o, out=8192, kdim=2048):
            sw = _weight_shapes(out, kdim, o_n=o_n)

            def f(x, v, meta, ov, om):
                return fused_sparse_linear(x, v, meta, ov, om, n=8, m=16,
                                           o_n=o_n, block_o=block_o,
                                           interpret=False)
            closed = jax.jit(f).trace(
                jax.ShapeDtypeStruct((32, kdim), jnp.bfloat16),
                sw.nm_values, sw.nm_meta, sw.o_values, sw.o_meta).jaxpr
            return _kernel_eqns(closed)

        base = eqns(16, 256)
        assert base < 200
        assert eqns(32, 256) == base
        assert eqns(16, 128) == base
        assert eqns(16, 256, out=2048, kdim=2048) == base

    @pytest.mark.parametrize("out,kdim,outliers,quantize,serves", [
        (8192, 2048, True, False, True),       # internlm2 w_up
        (2048, 8192, True, True, True),        # w_down, int8 values
        (1024, 2048, False, False, True),      # no outliers
        (128, 128, False, False, True),        # narrow in, no outliers
        (128, 64, False, False, False),        # in under 8 m-blocks
        (64, 512, True, False, False),         # out under one lane tile
        (1000, 2048, True, False, False),      # out not lane-aligned
        (256, 384, False, False, True),        # odd in, no outliers
    ])
    def test_kernel_serves(self, out, kdim, outliers, quantize, serves,
                           monkeypatch):
        """On a TPU the kernel takes weights whose out dim fills 128-lane
        tiles and whose in dim splits into 256-wide outlier groups (or
        whole 8-block rows without outliers); the CPU and a program
        partitioned over a mesh keep the portable path."""
        from repro.models.sparse_serving import kernel_serves
        from repro.parallel import policy as pol
        sw = _weight_shapes(out, kdim, quantize, outliers)
        assert not kernel_serves(sw)                  # this host is a CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert kernel_serves(sw) == serves
        with pol.suspended(partitioned=True):         # placement's jits
            assert not kernel_serves(sw)
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        with pol.activation_sharding(mesh):           # dry runs
            assert not kernel_serves(sw)
            with pol.suspended():                     # an engine inside
                assert kernel_serves(sw) == serves

    @pytest.mark.parametrize("out,kdim", SERVED)
    def test_served_shapes_take_the_kernel(self, out, kdim, monkeypatch):
        """Every served projection takes the kernel on a TPU, in every
        stored format."""
        from repro.models.sparse_serving import kernel_serves
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for quantize, outliers in ((False, True), (True, True),
                                   (False, False)):
            assert kernel_serves(_weight_shapes(out, kdim, quantize,
                                                outliers))

    @pytest.mark.parametrize("name", ["internlm2-1.8b.s816",
                                      "mistral-7b.s816"])
    def test_projection_paths_of_served_models(self, name, monkeypatch):
        """Layers x kinds per step-program call, all on the kernel on a TPU
        and all portable on the CPU or under a mesh."""
        from bench import harness, registry, weights
        from repro.models.sparse_serving import projection_paths
        conf = registry.config(name)
        m = conf["model"]
        params = jax.eval_shape(
            weights.params_fn(m, harness.compressor(conf["weights"])),
            jax.random.PRNGKey(0))
        n = 7 * m["n_layers"]
        assert projection_paths(params) == {"kernel": 0, "portable": n}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert projection_paths(params) == {"kernel": n, "portable": 0}
        assert projection_paths(params, partitioned_program=True) == {
            "kernel": 0, "portable": n}

    def test_deployed_bytes_ratio(self):
        from repro.models.sparse_serving import _to_sparse_weight
        w = jax.random.normal(jax.random.PRNGKey(0), (512, 1024), jnp.bfloat16)
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
        sw = _to_sparse_weight(w, cfg)
        ratio = sw.deployed_bytes() / (w.size * 2)
        # 8:16 values (1.0 B/e) + 4-bit packed idx (0.25) + 16:256 outliers
        # (0.125 + 0.0625) = 1.4375 B/e vs dense 2 B/e  => 0.719
        # (the paper's 0.875 BITS/e figure assumes enumerative silicon
        #  decoding; the software TPU layout spends 2 bits/e on 4-bit idx)
        assert ratio == pytest.approx(0.71875, abs=1e-3)


class TestQuantizedSparse:
    """Beyond-paper: int8 N:M values + exact bf16 outliers."""

    def test_int8_accuracy_and_bytes(self):
        from repro.models.sparse_serving import _to_sparse_weight, sparse_apply
        w = jax.random.normal(jax.random.PRNGKey(0), (128, 512)) * 0.05
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 512))
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
        sl = sparsify_linear(w, None, cfg)
        y_ref = x @ dense_effective_weight(w, sl, cfg).T
        sw_bf = _to_sparse_weight(w, cfg)
        sw_q = _to_sparse_weight(w, cfg, quantize=True)
        y_q = sparse_apply(sw_q, x)
        # int8 error stays below 1% of output RMS
        rms = float(jnp.sqrt(jnp.mean(y_ref ** 2)))
        assert float(jnp.abs(y_q - y_ref).max()) < 0.05 * rms
        assert sw_q.deployed_bytes() < 0.45 * sw_bf.deployed_bytes()

    def test_outliers_stay_exact_under_quant(self):
        from repro.models.sparse_serving import _to_sparse_weight
        w = jax.random.normal(jax.random.PRNGKey(2), (64, 512))
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
        sw = _to_sparse_weight(w, cfg, quantize=True)
        assert sw.nm_values.dtype == jnp.int8
        assert sw.o_values.dtype == w.dtype          # outliers uncompressed

    def test_deployed_bytes_counts_v_scale(self):
        """Regression: int8 mode must bill the per-row f32 scales too, or
        benchmark compression ratios overstate the int8 savings."""
        from repro.models.sparse_serving import _to_sparse_weight
        w = jax.random.normal(jax.random.PRNGKey(3), (64, 512))
        cfg = SparsifyConfig(scorer="magnitude", use_smoothquant=False)
        sw = _to_sparse_weight(w, cfg, quantize=True)
        without_scale = sum(
            v.size * v.dtype.itemsize
            for v in (sw.nm_values, sw.nm_meta, sw.o_values, sw.o_meta))
        assert sw.v_scale is not None
        assert sw.deployed_bytes() == without_scale + sw.v_scale.size * 4
