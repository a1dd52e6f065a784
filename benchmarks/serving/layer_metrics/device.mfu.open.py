"""device.mfu.open: see ``servbench.readers.mfu``."""
from servbench.readers import mfu as read  # noqa: F401
