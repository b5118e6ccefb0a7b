from . import capture  # noqa: F401  (jax-free trace-capture hook)

try:
    from .kernel import paged_decode_attention  # noqa: F401
    from .ref import paged_decode_ref  # noqa: F401
except ImportError as e:  # jax absent: capture geometry stays importable
    if not (e.name or "").startswith("jax"):
        raise  # a real break in kernel/ref must not be masked
