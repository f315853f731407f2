"""The wavefront kernel's roofline share, %: see readers.py."""
from chipbench.readers import wavefront_roofline as read  # noqa: F401
