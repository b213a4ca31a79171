"""Device: 1 - (union of the device-op intervals) / traced seconds, in
percent."""
from harness.readers import device_idle as read  # noqa: F401
