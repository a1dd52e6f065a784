"""kernel.decode_attn_roofline.open: see ``servbench.readers.decode_attn_roofline``."""
from servbench.readers import decode_attn_roofline as read  # noqa: F401
