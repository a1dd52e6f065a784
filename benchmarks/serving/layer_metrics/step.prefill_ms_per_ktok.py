"""step.prefill_ms_per_ktok: see ``servbench.readers.prefill_ms_per_ktok``."""
from servbench.readers import prefill_ms_per_ktok as read  # noqa: F401
