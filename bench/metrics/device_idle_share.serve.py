"""Share of the serving window in which no operation ran on the device:
one minus the union of the device's operation intervals over the window."""
from yard.readers import idle_share_pct


def read(w):
    return idle_share_pct(w)
