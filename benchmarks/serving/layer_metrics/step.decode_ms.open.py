"""step.decode_ms.open: see ``servbench.readers.decode_ms``."""
from servbench.readers import decode_ms as read  # noqa: F401
