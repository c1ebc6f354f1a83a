"""Share of the traced stretch in which no operation ran on the device:
1 - union of the device's op intervals over the stretch's length."""

from benchmark.drivers_common import idle_percent as read  # noqa: F401
