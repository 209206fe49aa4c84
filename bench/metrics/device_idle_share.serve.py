"""Share of the traced window in which no operation ran on the chip
(1 - busy / window), averaged over the cell's chips."""
import xplane


def reduce(tr, facts):
    busy = sum(xplane.busy_ns(tr, d) for d in tr.devices) / len(tr.devices)
    return 100.0 * (1.0 - busy / tr.window_ns)
