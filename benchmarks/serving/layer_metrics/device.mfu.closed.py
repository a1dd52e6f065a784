"""device.mfu.closed: see ``servbench.readers.mfu``."""
from servbench.readers import mfu as read  # noqa: F401
