"""Pallas TPU kernels of the scheduler (``relaxed_topk``) and the model
stack (``flash_attention``), their jitted wrappers (``ops``) and pure-jnp
oracles (``ref``)."""
import jax


def default_interpret() -> bool:
    """Backend-derived default for every kernel's ``interpret=``: compiled
    Pallas on TPU (the kernels are written for Mosaic and have never been
    validated under a Triton lowering), interpret mode everywhere else
    (CPU/GPU; interpret is the validation vehicle, DESIGN.md §7.2)."""
    return jax.default_backend() != "tpu"
