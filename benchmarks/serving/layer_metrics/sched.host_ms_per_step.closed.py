"""sched.host_ms_per_step.closed: see ``servbench.readers.host_ms_per_step``."""
from servbench.readers import host_ms_per_step as read  # noqa: F401
