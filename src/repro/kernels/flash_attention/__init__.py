from . import capture  # noqa: F401  (jax-free trace-capture hook)

try:
    from .kernel import flash_attention  # noqa: F401
    from .ref import attention_ref  # noqa: F401
except ImportError as e:  # jax absent: capture geometry stays importable
    if not (e.name or "").startswith("jax"):
        raise  # a real break in kernel/ref must not be masked
