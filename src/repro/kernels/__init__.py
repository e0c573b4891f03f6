"""Pallas TPU kernels, each with a pure-jnp reference (``ref.py``)."""
import jax


def check_backend(interpret: bool) -> None:
    """Refuse to run a TPU kernel off a TPU unless the caller asked for the
    Pallas interpreter; nothing picks interpret mode on its own."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas TPU kernel called on backend {jax.default_backend()!r}; "
            "pass interpret=True to run it in the Pallas interpreter")
