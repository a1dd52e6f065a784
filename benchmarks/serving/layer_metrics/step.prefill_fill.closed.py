"""step.prefill_fill.closed: see ``servbench.readers.prefill_fill``."""
from servbench.readers import prefill_fill as read  # noqa: F401
