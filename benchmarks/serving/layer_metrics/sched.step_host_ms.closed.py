"""sched.step_host_ms.closed: see ``servbench.spans.step_host_ms``."""
from servbench.spans import step_host_ms as read  # noqa: F401
