"""sched.host_ms_per_step.open: see ``servbench.readers.host_ms_per_step``."""
from servbench.readers import host_ms_per_step as read  # noqa: F401
