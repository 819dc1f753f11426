"""The Pallas TPU kernel for N:M-compressed weights
(``fused_sparse_linear``, with or without its outlier stream, served
through ``models/sparse_serving.sparse_apply``) and the jnp oracles the
tests hold it to (``ref``)."""
import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas kernels compile with Mosaic on a TPU backend and run in
    interpret mode elsewhere, decided when the kernel is traced; an explicit
    bool wins (the AOT tests compile for a described TPU from a CPU host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret

