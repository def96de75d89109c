"""Pallas TPU kernels for the paper's compute hot-spots.

Layout: one subpackage per kernel with ``kernel.py`` (pallas_call +
BlockSpec), ``ops.py`` (jit'd wrapper incl. packing), ``ref.py`` (pure-jnp
oracle). ``segment_ops`` is the backend dispatcher used by the GNN layers;
``gather_segsum`` is the fused gather->segment-aggregate family behind
``agg_backend='pallas'`` (plan-fed, jit/grad-safe). The full contract —
layouts, sentinels, repad invariants, how to add a kernel — is
docs/KERNELS.md.
"""
import jax


def pallas_interpret() -> bool:
    """Interpret mode for the Pallas kernels on the backend JAX runs on.

    The CPU runs the Pallas interpreter; a TPU compiles the kernels with
    Mosaic. Any other platform has no kernel path here and raises, so a
    kernel never runs interpreted on an accelerator by accident.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel path for platform {platform!r}: the CPU runs the "
        "interpreter and a TPU compiles the kernels"
    )
