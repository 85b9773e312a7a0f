"""Share of the traced window in which no operation ran on the chip.
Layer: device."""
from chipbench.metrics._util import idle_pct as read  # noqa: F401
