"""Share of the serving window in which no operation ran on the device (%)."""
from lib.readers import idle_share_pct


def read(data):
    return idle_share_pct(data)
