"""Idle share of the device over the window, %: see readers.py."""
from chipbench.readers import device_idle_share as read  # noqa: F401
