"""device.idle_share.closed: see ``servbench.readers.idle_share``."""
from servbench.readers import idle_share as read  # noqa: F401
