"""pool.prefix_hit_rate: see ``servbench.readers.prefix_hit_rate``."""
from servbench.readers import prefix_hit_rate as read  # noqa: F401
