"""Pallas TPU kernels for N:M-compressed weights (``nm_spmm``,
``outlier_spmm``, ``fused_sparse_linear``), their jnp oracles (``ref``) and
the public entry points (``ops``)."""
import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas kernels compile with Mosaic on a TPU backend and run in
    interpret mode elsewhere, decided when the kernel is traced; an explicit
    bool wins (the AOT tests compile for a described TPU from a CPU host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
