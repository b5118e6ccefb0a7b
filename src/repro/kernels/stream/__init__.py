from . import capture  # noqa: F401  (jax-free trace-capture hook)
from .capture import bytes_moved  # noqa: F401

try:
    from .kernel import (  # noqa: F401
        stream_add,
        stream_copy,
        stream_scale,
        stream_triad,
    )
    from . import ref  # noqa: F401
except ImportError as e:  # jax absent: capture geometry stays importable
    if not (e.name or "").startswith("jax"):
        raise  # a real break in kernel/ref must not be masked
