"""Pallas TPU kernels for the fused-op inventory (reference:
`paddle/phi/kernels/fusion/gpu/` CUDA kernels -> Mosaic/Pallas here)."""
import jax


def _on_tpu() -> bool:
    """Whether JAX's default backend is a TPU — the one switch every kernel
    entry routes on (Mosaic kernel vs its XLA oracle).  A backend that fails
    to initialise raises here: quietly answering False would send a TPU
    host's traffic to the XLA reference and hide the broken device."""
    return jax.devices()[0].platform == "tpu"


def _out_struct(shape, dtype, *like):
    """`out_shape` entry of a `pallas_call` whose output varies over the same
    manual mesh axes as the `like` operands: inside `jax.shard_map` the
    kernel runs per shard and JAX needs that stated (`vma`); outside any
    manual region the set is empty and this is a plain ShapeDtypeStruct."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
